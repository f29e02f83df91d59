"""90th percentile of the wait from a request's due time to its
admission into a lane (host clock), over the requests admitted."""

import numpy as np


def read(rec):
    waits = [(g.admitted - g.due) * 1e3 for g in rec["requests"]
             if g.admitted is not None]
    return float(np.percentile(waits, 90)) if waits else None
