"""Share of the time admission was blocked in the traced window that
the page pool blocked it, in %: the program's ``admission.blocked``
spans (from one admission pass that left the queue non-empty to the
next) with ``by=pages`` (a lane was free, the pool refused the head's
reservation) over all of them, each counted by its overlap with the
window.  0 when no admission was blocked in the window; `note` gives
the blocked milliseconds by cause."""

from bench.metrics._spans import last_serve, window


def _blocked_ms(rec):
    spans, w = last_serve(), window(rec)
    if spans is None or w is None:
        return None
    by = {"pages": 0.0, "lanes": 0.0}
    for s in spans:
        if s.name != "admission.blocked":
            continue
        overlap = min(s.t1, w[1]) - max(s.t0, w[0])
        if overlap > 0:
            cause = (s.data or {}).get("by")
            by[cause] = by.get(cause, 0.0) + overlap * 1e3
    return by, (w[1] - w[0]) * 1e3


def read(rec):
    got = _blocked_ms(rec)
    if got is None:
        return None
    total = sum(got[0].values())
    return 100.0 * got[0]["pages"] / total if total > 0 else 0.0


def note(rec):
    got = _blocked_ms(rec)
    if got is None:
        return None
    by, span = got
    return (f"blocked {sum(by.values()):.1f} ms of the window's "
            f"{span:.1f} ms: " + ", ".join(f"by {k} {v:.1f} ms"
                                           for k, v in sorted(by.items())))
