"""Mean device time of the traced steps that carried no prefill chunk
(the step program's executions in the trace), in ms."""

from bench.flops import split_steps


def read(rec):
    pick = split_steps(rec, chunk=False)
    if pick is None:
        return None
    return 1e3 * sum(t for _, t in pick) / len(pick)
