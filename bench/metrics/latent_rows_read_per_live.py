"""Latent-cache rows the step's MLA attention read per live position
among them, over the traced window's steps (``latent_rows_read`` and
``latent_rows_live`` of the program's ``engine.step`` span: every lane's
whole page table in decode, each prefilling lane's in the chunk sweep,
against the positions the attending lanes hold).  What a paged
latent-attention kernel that reads only live pages would save."""

from bench.metrics._spans import in_window


def read(rec):
    got = in_window(rec, "engine.step")
    if got is None:
        return None
    data = [s.data or {} for s in got[1]]
    read_ = sum(d.get("latent_rows_read", 0) for d in data)
    live = sum(d.get("latent_rows_live", 0) for d in data)
    return read_ / live if live else None
