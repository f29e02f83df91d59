"""Reduce a `jax.profiler` trace of the measured window to numbers.

The traced window is the span of the benchmark's own host annotations,
one ``bench_step`` per fused step (`bench/serve.py` writes them around
each step, and ``bench_admit`` / ``bench_release`` around each lane's
admission and release).  Within it:

  * device busy: the union of the intervals in which an operation ran
    on the device, averaged over the devices; the idle share is one
    minus busy over the window;
  * per-kernel device time: the summed durations of the operations
    whose name (or any stat, such as the HLO op it lowers) names the
    kernel;
  * per-step device time: the durations of the step program's module
    executions (``jit_step``), one per traced step and in order;
  * a breakdown: the operations that took most device time, summed by
    name over their instances (leaves only: a conditional or loop whose
    body ran is not counted again),
    and the longest idle gaps named by the innermost host span that
    covers the gap (what the host was doing).

`read` finds the planes: a TPU device plane has an "XLA Ops" and an
"XLA Modules" line; on the CPU, which tests use, the operations are the
events that carry an ``hlo_op`` stat and a module is one ``run_id``.
"""

from __future__ import annotations

import collections
import glob
import os
import re

__all__ = ["read", "reduce", "union_ns", "find_xplane"]

STEP_SPAN = "bench_step"
KERNELS = ("paged_attention", "paged_prefill")


def find_xplane(logdir: str) -> str:
    found = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return found[-1]


def _stats(ev) -> dict:
    try:
        return {k: v for k, v in ev.stats}
    except Exception:   # a stat the binding cannot convert
        return {}


def read(path: str) -> dict:
    """Events of a trace file as plain tuples:
    ``{"devices": [{"ops": [...], "modules": [...]}, ...], "host":
    [...]}``, each event ``(name, start_ns, end_ns, text)`` where text
    joins the stat values that name what the event ran."""
    import warnings

    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    with warnings.catch_warnings():
        # the binding's stats type warns on every read
        warnings.simplefilter("ignore", DeprecationWarning)
        return _read(pd)


def _read(pd) -> dict:
    devices, host = [], []
    cpu_ops: list = []
    cpu_runs: dict = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            ops, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops += [_event(e) for e in line.events]
                elif line.name == "XLA Modules":
                    mods += [_event(e) for e in line.events]
            if ops or mods:
                devices.append({"ops": ops, "modules": mods})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    st = _stats(e)
                    if "hlo_op" in st and e.duration_ns > 0:
                        cpu_ops.append(_event(e, st))
                        key = (st.get("hlo_module"), st.get("run_id"))
                        lo, hi = cpu_runs.get(key, (e.start_ns, 0))
                        cpu_runs[key] = (min(lo, e.start_ns),
                                         max(hi, e.start_ns + e.duration_ns))
                    else:
                        host.append((e.name, e.start_ns,
                                     e.start_ns + e.duration_ns, ""))
    if not devices and cpu_ops:
        mods = [(str(k[0]), lo, hi, "") for k, (lo, hi) in cpu_runs.items()]
        devices.append({"ops": cpu_ops, "modules": sorted(
            mods, key=lambda m: m[1])})
    return {"devices": devices, "host": host}


def _event(e, st=None) -> tuple:
    st = _stats(e) if st is None else st
    text = " ".join(str(v) for k, v in st.items()
                    if k in ("hlo_op", "long_name", "tf_op", "name",
                             "hlo_category", "kernel_details"))
    return (e.name, e.start_ns, e.start_ns + e.duration_ns, text)


def union_ns(intervals) -> float:
    """Total length covered by the (start, end) intervals."""
    total, cur_lo, cur_hi = 0.0, None, None
    for lo, hi in sorted(intervals):
        if cur_hi is None or lo > cur_hi:
            if cur_hi is not None:
                total += cur_hi - cur_lo
            cur_lo, cur_hi = lo, hi
        else:
            cur_hi = max(cur_hi, hi)
    if cur_hi is not None:
        total += cur_hi - cur_lo
    return total


def _clip(ev, lo, hi):
    return max(ev[1], lo), min(ev[2], hi)


def reduce(events: dict, *, step_module: str = r"^jit_step\b",
           kernels=KERNELS, top: int = 10) -> dict:
    """Numbers of the traced window (seconds unless said)."""
    steps = sorted((h for h in events["host"] if h[0] == STEP_SPAN),
                   key=lambda h: h[1])
    if not steps:
        raise ValueError(f"no {STEP_SPAN} spans in the trace")
    lo, hi = steps[0][1], steps[-1][2]
    window = (hi - lo) * 1e-9
    busy, per_kernel, per_op = [], collections.Counter(), \
        collections.Counter()
    step_dev: list = []
    gaps: list = []
    pat = re.compile(step_module)
    n_dev = max(1, len(events["devices"]))
    for dev in events["devices"]:
        ivs = []
        for ev in dev["ops"]:
            a, b = _clip(ev, lo, hi)
            if b <= a:
                continue
            ivs.append((a, b))
            if _CONTAINER.search(ev[0]):
                continue        # its body's operations are counted
            per_op[_short(ev[0])] += (b - a) * 1e-9 / n_dev
            label = f"{ev[0]} {ev[3]}"
            for k in kernels:
                if k in label:
                    per_kernel[k] += (b - a) * 1e-9 / n_dev
        busy.append(union_ns(ivs) * 1e-9)
        mods = [m for m in dev["modules"]
                if pat.search(m[0]) and m[1] >= lo and m[2] <= hi]
        step_dev.append([(m[2] - m[1]) * 1e-9 for m in mods])
        gaps += _gaps(sorted(ivs), lo, hi)
    busy_s = sum(busy) / n_dev
    per_step = None
    if step_dev and all(len(d) == len(steps) for d in step_dev):
        per_step = [sum(x) / n_dev for x in zip(*step_dev)]
    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    return {
        "window_s": window,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window if window > 0 else None,
        "kernels": dict(per_kernel),
        "n_steps": len(steps),
        "step_device_s": per_step,
        "device_ops": [[n, s] for n, s in per_op.most_common(top)],
        "idle_gaps": [[_host_name(events["host"], a, b), (b - a) * 1e-9]
                      for a, b in gaps[:top]],
    }


# an operation that only runs others (its event spans theirs)
_CONTAINER = re.compile(r"\s(conditional|while|call)\(")


def _short(name: str) -> str:
    """An operation's name without the HLO text the TPU trace appends and
    without its instance number: every call site of a kernel, or every
    fusion, sums under one name."""
    return re.sub(r"\.\d+$", "", name.split(" = ")[0].lstrip("%"))[:120]


def _gaps(ivs, lo, hi) -> list:
    out, cur = [], lo
    for a, b in ivs:
        if a > cur:
            out.append((cur, a))
        cur = max(cur, b)
    if hi > cur:
        out.append((cur, hi))
    return out


def _host_name(host, lo, hi) -> str:
    """The innermost host span that covers the whole gap, else the
    innermost one that covers its middle."""
    for cover in (lambda e: e[1] <= lo and e[2] >= hi,
                  lambda e: e[1] <= (lo + hi) / 2 <= e[2]):
        best = None
        for ev in host:
            if cover(ev) and (best is None
                              or ev[2] - ev[1] < best[2] - best[1]):
                best = ev
        if best is not None:
            return best[0]
    return "(no host span)"
