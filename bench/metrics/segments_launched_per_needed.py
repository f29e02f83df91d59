"""Lane-segments the fused step computed per lane-segment a lane
needed: each launched segment runs every lane, so seg_batch x n_lanes
over seg_policy, summed over the window's steps (the step's counters)."""


def read(rec):
    need = sum(s["seg_policy"] for s in rec["steps"])
    ran = sum(s["seg_batch"] for s in rec["steps"]) * rec["n_lanes"]
    return ran / need if need else None
