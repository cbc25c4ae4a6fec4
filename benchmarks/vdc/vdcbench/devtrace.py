"""Reduction of a JAX profiler trace to the window's device intervals,
host spans and the ``breakdown`` lists.

A trace (``<dir>/plugins/profile/<time>/*.xplane.pb``) holds one plane per
TPU (``/device:TPU:<n>``) whose ``XLA Modules`` line has one event per
program execution, and host planes whose events include the benchmark's
``vdc.*`` annotations.  Both are on the trace's clock, in nanoseconds from
the profile's start.  :func:`extract` keeps only what the metrics read, as
plain lists, so the reductions below run the same on a live trace and on
the small recorded one in ``tests/data``.
"""
from __future__ import annotations

import collections
import glob
import os

DEVICE_PREFIX = "/device:TPU:"
DEVICE_LINE = "XLA Modules"
WINDOW = "vdc.window"


# A traced run compiles its programs without per-HLO-op trace points: the
# bank's nested scans emit ~30 op events per microsecond of device time,
# which no trace of a whole window can hold.  Program executions (the
# ``XLA Modules`` line) are still traced, and that is what the metrics read.
NO_OP_TRACE_FLAG = "--xla_enable_hlo_trace=false"


def drop_op_trace_points() -> None:
    """Add the flag to ``LIBTPU_INIT_ARGS``; call before JAX starts the
    TPU."""
    args = os.environ.get("LIBTPU_INIT_ARGS", "")
    if NO_OP_TRACE_FLAG not in args.split():
        os.environ["LIBTPU_INIT_ARGS"] = (args + " " + NO_OP_TRACE_FLAG).strip()


def profile_options():
    """No Python function tracing; host events down to the benchmark's
    annotations."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def extract(path: str) -> dict:
    """``{"window": [start, end] or None, "device": {plane: [[name, start,
    dur], ...]}, "host": [[name, start, dur], ...]}`` in ns, from one
    ``.xplane.pb``.  Host events are the ``vdc.*`` annotations only."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    device: dict[str, list] = {}
    host: list = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            evs = device.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == DEVICE_LINE:
                    evs.extend([e.name, e.start_ns, e.duration_ns]
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, e.start_ns, e.duration_ns]
                            for e in line.events if e.name.startswith("vdc."))
    window = None
    for name, start, dur in host:
        if name == WINDOW:
            window = [start, start + dur]
    return {"window": window, "device": device, "host": host}


def union(intervals: list, lo: float, hi: float) -> list:
    """Disjoint sorted union of ``[start, end)`` intervals clipped to
    ``[lo, hi)``."""
    out: list = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def busy_ns(tr: dict, plane: str) -> float:
    lo, hi = tr["window"]
    return sum(e - s for s, e in union(
        [(st, st + d) for _, st, d in tr["device"][plane]], lo, hi))


def gaps(tr: dict, plane: str) -> list:
    """Idle stretches of one device over the window, ``[start, end)``."""
    lo, hi = tr["window"]
    busy = union([(st, st + d) for _, st, d in tr["device"][plane]], lo, hi)
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = e
    if hi > t:
        out.append((t, hi))
    return out


def host_label(tr: dict, t: float) -> str:
    """The innermost ``vdc.*`` layer span running at ``t`` (the one that
    started last), or ``engine`` when none is: the replay outside the
    wrapped layers."""
    best, best_start = "engine", None
    for name, st, d in tr["host"]:
        if name in (WINDOW, "vdc.job") or not st <= t < st + d:
            continue
        if best_start is None or st > best_start:
            best, best_start = name[len("vdc."):], st
    return best


def breakdown(tr: dict, plane: str, top: int = 10) -> dict:
    """The device operations that took most time and the longest idle gaps,
    each gap named by the host span running at its midpoint."""
    lo, hi = tr["window"]
    per_op: dict[str, float] = collections.defaultdict(float)
    for name, st, d in tr["device"][plane]:
        s, e = max(st, lo), min(st + d, hi)
        if e > s:
            per_op[name] += (e - s) / 1e9
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(gaps(tr, plane), key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[host_label(tr, (s + e) / 2), (e - s) / 1e9]
                          for s, e in idle]}
