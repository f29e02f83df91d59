"""Peak pages in use over the run as a share of the pool's pages
(`KVPool.stats()`: pages_peak / n_pages), in %."""


def read(rec):
    pool = rec["pool"]
    return 100.0 * pool["pages_peak"] / pool["n_pages"]
