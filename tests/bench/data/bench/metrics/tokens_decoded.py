"""Tests only: decoded tokens of the run (a metric added by a file)."""


def read(rec):
    return float(sum(g.n_decoded for g in rec["requests"]))
