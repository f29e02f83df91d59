"""Share of its roofline that the paged-prefill kernel reached over the
traced steps: the least time the chip needs for the chunks' attention
(bench/flops.py: real chunk rows, history pages) over the kernel's
device time, in %.  `note` names the bound."""

from bench.flops import roofline


def read(rec):
    r = roofline(rec, "paged_prefill")
    return None if r is None else r[0]


def note(rec):
    r = roofline(rec, "paged_prefill")
    return None if r is None else f"bound: {r[1]}"
