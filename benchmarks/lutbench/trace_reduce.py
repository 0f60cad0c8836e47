"""Reduce a profiler trace of one window to what the metrics read.

The JAX profiler writes ``<dir>/plugins/profile/<time>/*.xplane.pb``.
In it, each TPU is a plane ``/device:TPU:<n>`` whose line ``XLA Ops``
holds one event per device operation and whose line ``XLA Modules``
holds one event per program run; the host is the plane ``/host:CPU``
with a line per thread, where the benchmark's own spans
(``measure.span``) sit beside the runtime's.  All events share one
clock, in nanoseconds.

* window: the benchmark's ``lutbench.window`` span;
* busy: the union of the device operations' intervals inside the
  window, averaged over the devices that ran any;
* kernels: the device operations that are Mosaic custom calls (the
  Pallas kernels), their total time and the program runs that hold one;
* idle gaps: the stretches of the window with no device operation,
  each labelled by the benchmark span that covers most of it, else by
  the host event that does (either has to cover half the gap), and
  summed per label.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

from measure import WINDOW

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
KERNEL_MARK = "tpu_custom_call"     # Mosaic custom-call target
TOP = 10

Interval = Tuple[float, float]


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                   # averaged over devices
    kernel_s: float                 # all devices
    kernel_runs: int                # program runs holding a kernel
    device_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def latest_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: str):
    """ProfileData of an ``.xplane.pb`` file (or of gzipped bytes)."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        import gzip

        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def _events(line) -> Iterable[Tuple[str, float, float, dict]]:
    for e in line.events:
        yield e.name, e.start_ns, e.start_ns + e.duration_ns, e


def op_name(name: str) -> str:
    """``%fusion.3 = f32[...] ...`` (an op's HLO text) -> ``fusion.3``."""
    return name.split(" = ", 1)[0].lstrip("%")[:80]


def _is_kernel(name: str, event) -> bool:
    if KERNEL_MARK in name:
        return True
    for _, v in event.stats:
        if isinstance(v, str) and KERNEL_MARK in v:
            return True
    return False


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


class _HostIndex:
    """Host events of one kind, sorted, for overlap queries."""

    def __init__(self, events: List[Tuple[float, float, str]]):
        self.events = sorted(events)
        self.starts = [e[0] for e in self.events]
        self.longest = max((b - a for a, b, _ in self.events), default=0.0)

    def best(self, lo: float, hi: float) -> Optional[str]:
        """Name with the largest total overlap with [lo, hi), if it
        covers at least half of it."""
        i = bisect.bisect_left(self.starts, lo - self.longest)
        j = bisect.bisect_right(self.starts, hi)
        overlap: Dict[str, float] = collections.defaultdict(float)
        for a, b, name in self.events[i:j]:
            o = min(b, hi) - max(a, lo)
            if o > 0:
                overlap[name] += o
        if not overlap:
            return None
        name = max(overlap, key=overlap.get)
        return name if overlap[name] >= 0.5 * (hi - lo) else None


def reduce(pd) -> Summary:
    planes = {p.name: p for p in pd.planes}
    host = planes.get(HOST_PLANE)
    if host is None:
        raise ValueError("trace has no host plane")
    ours, others, window = [], [], None
    for line in host.lines:
        for name, a, b, _ in _events(line):
            if name == WINDOW:
                window = (a, b) if window is None else (min(window[0], a),
                                                        max(window[1], b))
            elif name.startswith("lutbench."):
                ours.append((a, b, name))
            else:
                others.append((a, b, name))
    if window is None:
        raise ValueError(f"trace has no {WINDOW} span")
    lo, hi = window

    busy_per_device, op_time = [], collections.Counter()
    kernel_s, kernel_runs, all_busy = 0.0, 0, []
    for pname, plane in planes.items():
        if not DEVICE_PLANE.match(pname):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        if OPS_LINE not in lines:
            continue
        intervals, kernel_iv = [], []
        for name, a, b, e in _events(lines[OPS_LINE]):
            a, b = max(a, lo), min(b, hi)
            if b <= a:
                continue
            intervals.append((a, b))
            op_time[op_name(name)] += (b - a) * 1e-9
            if _is_kernel(name, e):
                kernel_s += (b - a) * 1e-9
                kernel_iv.append((a, b))
        if not intervals:
            continue
        busy = union(intervals)
        busy_per_device.append(sum(b - a for a, b in busy) * 1e-9)
        all_busy.extend(busy)
        if MODULES_LINE in lines and kernel_iv:
            starts = sorted(a for a, _ in kernel_iv)
            for _, a, b, _ in _events(lines[MODULES_LINE]):
                k = bisect.bisect_left(starts, a)
                if k < len(starts) and starts[k] < b and lo <= a and b <= hi:
                    kernel_runs += 1
    if not busy_per_device:
        raise ValueError("no device operation ran in the window")

    ours_ix, others_ix = _HostIndex(ours), _HostIndex(others)
    idle: Dict[str, float] = collections.defaultdict(float)
    for a, b in gaps(union(all_busy), lo, hi):
        label = (ours_ix.best(a, b) or others_ix.best(a, b)
                 or "no host span over half the gap")
        idle[label] += (b - a) * 1e-9
    return Summary(
        window_s=(hi - lo) * 1e-9,
        busy_s=sum(busy_per_device) / len(busy_per_device),
        kernel_s=kernel_s, kernel_runs=kernel_runs,
        device_ops=op_time.most_common(TOP),
        idle_gaps=sorted(idle.items(), key=lambda kv: -kv[1])[:TOP])
