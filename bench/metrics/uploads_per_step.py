"""Host-to-device arrays a fused step creates, summed over the traced
window's steps per step (the ``uploads`` count of the program's
``engine.step`` span)."""

from bench.metrics._spans import in_window


def read(rec):
    got = in_window(rec, "engine.step")
    if got is None or not got[1]:
        return None
    steps = got[1]
    return sum((s.data or {}).get("uploads", 0) for s in steps) / len(steps)
