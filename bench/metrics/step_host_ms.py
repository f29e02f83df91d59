"""Mean host time of a fused step over the traced window: self time of
the program's ``engine.step`` span less its ``engine.sync`` child (the
wait for the device), in ms: planning, uploads, page ops and dispatch."""

from bench.metrics._spans import self_ms


def read(rec):
    return self_ms(rec, "engine.step", "engine.sync")
