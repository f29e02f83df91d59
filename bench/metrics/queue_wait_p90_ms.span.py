"""90th percentile of the wait from a request's arrival to its
admission into a lane, over every request admitted in the serve: the
durations of the program's ``request.queue`` spans, in ms (the
program-side twin of ``queue_wait_p90_ms``)."""

import numpy as np

from bench.metrics._spans import last_serve


def read(rec):
    spans = last_serve()
    if spans is None:
        return None
    waits = [s.duration * 1e3 for s in spans if s.name == "request.queue"]
    return float(np.percentile(waits, 90)) if waits else None
