"""The straggler of the expert layer: the rows of the busiest held
expert over the mean rows per held expert, per step summed over the
layers (``expert_rows_max`` over ``expert_rows_live`` / experts held,
from the program's ``engine.step`` span), averaged over the traced
window's steps that routed any row."""

from bench.metrics._spans import in_window


def read(rec):
    got = in_window(rec, "engine.step")
    held = (rec.get("sizes") or {}).get("experts_held")
    if got is None or not held:
        return None
    ratios = [d["expert_rows_max"] * held / d["expert_rows_live"]
              for d in (s.data or {} for s in got[1])
              if d.get("expert_rows_live")]
    return sum(ratios) / len(ratios) if ratios else None
