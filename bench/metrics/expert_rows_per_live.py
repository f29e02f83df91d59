"""Rows the held experts' grouped products compute per row that live
tokens routed to them, over the traced window's steps (the
``expert_rows`` and ``expert_rows_live`` counts of the program's
``engine.step`` span).  The products compute whole row tiles, once per
expert whose rows touch a tile, so the ratio reads how empty the tiles
run: one chip's share of the experts sees few rows each."""

from bench.metrics._spans import in_window


def read(rec):
    got = in_window(rec, "engine.step")
    if got is None:
        return None
    data = [s.data or {} for s in got[1]]
    rows = sum(d.get("expert_rows", 0) for d in data)
    live = sum(d.get("expert_rows_live", 0) for d in data)
    return rows / live if live else None
