"""Model FLOPs the traced decode-only steps needed (bench/flops.py) over
their device time times the chip's peak, in %."""

from bench.flops import step_mfu


def read(rec):
    return step_mfu(rec, chunk=False)
