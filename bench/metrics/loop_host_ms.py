"""Mean host time of the serve loop around a step over the traced
window: the program's ``server.iteration`` span less its step, over the
iterations that ran one, in ms: arrivals, admission, token accounting
and release (the benchmark's own per-step bookkeeping included).
`note` names the serve's longest iterations, such as those that start
and stop the profiler."""

from bench.metrics._spans import last_serve, self_ms, window


def read(rec):
    return self_ms(rec, "server.iteration", "engine.step", need_child=True)


def note(rec):
    spans, w = last_serve(), window(rec)
    if spans is None or w is None:
        return None
    iters = sorted((s for s in spans if s.name == "server.iteration"),
                   key=lambda s: s.duration, reverse=True)[:4]
    return (f"window {w[0]:.3f}-{w[1]:.3f} s; longest iterations: "
            + ", ".join(f"{1e3 * s.duration:.1f} ms at {s.t0:.3f} s"
                        for s in iters))
