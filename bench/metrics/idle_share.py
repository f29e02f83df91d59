"""Share of the traced window in which no operation ran on the device
(one minus the union of device operations over the window), in %."""


def read(rec):
    t = rec["trace"]
    if not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
