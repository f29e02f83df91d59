"""The program's own spans of the last serve, for the span metrics.

`Server.serve` records its spans into the process tracer
(`repro.serving.obs.trace.TRACER`), whose ring holds the last serve
until the next starts, so they outlive the run's program objects.  The
step window is that of the traced steps, ``rec["traced_steps"][0]["t1"]``
to ``rec["traced_steps"][-1]["t1"]``: the steps the device trace covers,
without the profiler's own start and stop.  Times are the serve's
clock, the one the step records use.

Every function returns None where there is nothing to read: a program
without the process tracer, a ring that dropped spans during the serve,
or no traced steps.
"""

from __future__ import annotations


def last_serve():
    """The spans of the last serve, or None."""
    try:
        from repro.serving.obs.trace import TRACER
    except ImportError:
        return None
    if getattr(TRACER, "spans_dropped", 1) or not TRACER.spans:
        return None
    return list(TRACER.spans)


def window(rec):
    steps = rec.get("traced_steps") or []
    if len(steps) < 2:
        return None
    return steps[0]["t1"], steps[-1]["t1"]


def in_window(rec, name: str):
    """(spans of the last serve, the spans called ``name`` that lie
    wholly inside the step window), or None."""
    spans, w = last_serve(), window(rec)
    if spans is None or w is None:
        return None
    inside = [s for s in spans
              if s.name == name and s.t0 >= w[0] and s.t1 <= w[1]]
    return spans, inside


def self_ms(rec, name: str, child: str, *, need_child: bool = False):
    """Mean over the window's ``name`` spans of their duration less that
    of their ``child`` children, in ms; with ``need_child``, over those
    that have such a child only."""
    got = in_window(rec, name)
    if got is None:
        return None
    spans, parents = got
    ids = {s.id: 0.0 for s in parents}
    has = set()
    for s in spans:
        if s.name == child and s.parent in ids:
            ids[s.parent] += s.duration
            has.add(s.parent)
    keep = [s for s in parents if not need_child or s.id in has]
    if not keep:
        return None
    return 1e3 * sum(s.duration - ids[s.id] for s in keep) / len(keep)
