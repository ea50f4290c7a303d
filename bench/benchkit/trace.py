"""Profiler capture, and its reduction to device busy time, kernel time,
the costliest device operations and the longest idle gaps.

The reduction works on a neutral form of the trace, so that a small
recorded one can be kept beside the tests:

    {"device": {"<device>": [[op, start_ns, dur_ns], ...]},
     "host": [[span, start_ns, dur_ns], ...]}

``device`` holds the operations of each chip (the ``XLA Ops`` line of
its plane), each named by its HLO instruction without the numeric
suffix (``%viterbi_step.6 = ... custom-call(...)`` becomes
``viterbi_step``); ``host`` holds the benchmark's own spans
(``bench:*``).
"""
from __future__ import annotations

import glob
import os
import shutil
from pathlib import Path
from typing import Dict, List, Optional

SPAN_PREFIX = "bench:"
OPS_LINE = "XLA Ops"
# Operations that only hold others (a loop's span covers its body's).
CONTAINERS = ("while", "conditional", "call")


def span(name: str):
    """A host span the profiler records (cheap when it is off)."""
    import jax.profiler

    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


class Capture:
    """One profiler session, written under ``root`` (emptied first)."""

    def __init__(self, root: Path):
        self.root = Path(root)
        self.active = False

    def start(self) -> None:
        import jax.profiler

        shutil.rmtree(self.root, ignore_errors=True)
        self.root.mkdir(parents=True)
        jax.profiler.start_trace(str(self.root))
        self.active = True

    def stop(self) -> Optional[dict]:
        """Stop and return the neutral form (None if nothing was found)."""
        import jax.profiler

        if not self.active:
            return None
        jax.profiler.stop_trace()
        self.active = False
        files = glob.glob(os.path.join(self.root, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if not files:
            return None
        return load_xplane(files[0])


def op_name(hlo: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion``."""
    head = hlo.split(" = ", 1)[0].lstrip("%")
    base, _, suffix = head.rpartition(".")
    return base if base and suffix.isdigit() else head


def load_xplane(path: str) -> dict:
    """Neutral form of one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device: Dict[str, List[list]] = {}
    host: List[list] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                device[plane.name] = [
                    [op_name(ev.name), ev.start_ns, ev.duration_ns]
                    for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        host.append([ev.name[len(SPAN_PREFIX):],
                                     float(ev.start_ns),
                                     float(ev.duration_ns)])
    return {"device": device, "host": host}


def union(intervals) -> List[List[float]]:
    """Merge ``[start, end]`` intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def window_bounds(trace: dict):
    """``(start, end)`` of the traced window: the host spans' extent, or
    the device operations' where there are no spans."""
    pts = [(s, s + d) for _, s, d in trace["host"]]
    if not pts:
        pts = [(e[1], e[1] + e[2]) for evs in trace["device"].values()
               for e in evs]
    if not pts:
        return None
    return min(p[0] for p in pts), max(p[1] for p in pts)


def busy_seconds(trace: dict) -> Optional[float]:
    """Seconds in which an operation ran, averaged over the chips; None
    when the trace holds no device operation."""
    per = []
    for evs in trace["device"].values():
        merged = union([[e[1], e[1] + e[2]] for e in evs])
        per.append(sum(e - s for s, e in merged) / 1e9)
    if not per or not any(per):
        return None
    return sum(per) / len(per)


def kernel_events(trace: dict, kernel: str) -> List[list]:
    """Operations of every chip named ``kernel``."""
    return [e for evs in trace["device"].values() for e in evs
            if e[0] == kernel]


def top_ops(trace: dict, k: int = 10) -> List[list]:
    """The ``k`` device operations that took most time, in seconds summed
    over chips and over every instance of the operation (loops and other
    :data:`CONTAINERS` left out: their time is their body's)."""
    total: Dict[str, float] = {}
    for evs in trace["device"].values():
        for name, _, dur in evs:
            if name not in CONTAINERS:
                total[name] = total.get(name, 0.0) + dur / 1e9
    return sorted(([n, s] for n, s in total.items()),
                  key=lambda x: -x[1])[:k]


def idle_gaps(trace: dict, k: int = 10) -> List[list]:
    """The ``k`` longest idle gaps of the first chip inside the traced
    window, each named by the innermost host span around its middle
    (``"no span"`` where none was open)."""
    bounds = window_bounds(trace)
    if bounds is None or not trace["device"]:
        return []
    first = sorted(trace["device"])[0]
    merged = union([[e[1], e[1] + e[2]] for e in trace["device"][first]])
    gaps, cursor = [], bounds[0]
    for s, e in merged:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if bounds[1] > cursor:
        gaps.append((cursor, bounds[1]))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:k]:
        mid = 0.5 * (s + e)
        around = [h for h in trace["host"] if h[1] <= mid <= h[1] + h[2]]
        name = min(around, key=lambda h: h[2])[0] if around else "no span"
        out.append([name, (e - s) / 1e9])
    return out


def idle_share(trace: Optional[dict], window_s: Optional[float]):
    """Percent of the traced window in which no operation ran on the
    chip; None without a trace or a device operation."""
    if not trace or not window_s:
        return None
    busy = busy_seconds(trace)
    if busy is None:
        return None
    return 100.0 * max(0.0, 1.0 - busy / window_s)
