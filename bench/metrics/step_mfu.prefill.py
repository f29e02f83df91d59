"""Model FLOPs the traced chunk-carrying steps needed (their prefill
chunks and the lanes decoding beside them, bench/flops.py) over their
device time times the chip's peak, in %."""

from bench.flops import step_mfu


def read(rec):
    return step_mfu(rec, chunk=True)
