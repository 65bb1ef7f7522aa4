"""Reduce a profiler trace (``.xplane.pb``) to the device's busy time, the
time of each operation, and the longest idle gaps.

Device planes are ``/device:TPU:<n>``. A device is busy while a program
(line ``XLA Modules``) or an operation (line ``XLA Ops``) runs on it; the
window is the host annotation the benchmark puts around its measured
window, and every interval is clipped to it. Operation time sums the
``XLA Ops`` events by their HLO text, so a kernel is found by the name of
its instruction. An idle gap is named by the host event on a Python thread
line (``python``, ``python3``) that overlaps it most: what the host was doing while the
device waited."""
from __future__ import annotations

import glob
import gzip
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

BUSY_LINES = ("XLA Modules", "XLA Ops")
OP_LINE = "XLA Ops"


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def clip(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def gaps_between(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def op_name(hlo_text: str) -> str:
    """``%sparse_adagrad_op.1 = (...) custom-call(...)`` -> ``sparse_adagrad_op``."""
    m = re.match(r"%?([A-Za-z_][\w\-]*?)(?:\.\d+)?\s*=", hlo_text)
    return m.group(1) if m else hlo_text


@dataclass
class Summary:
    window_s: float
    busy_s: float  # mean over the devices
    ops: Dict[str, Tuple[int, float]] = field(default_factory=dict)  # name -> (count, s)
    idle: Dict[str, float] = field(default_factory=dict)  # host activity -> idle s

    def op_seconds(self, name: str) -> float:
        """Device seconds of the operations whose instruction is ``name``."""
        return self.ops.get(name, (0, 0.0))[1]

    def op_count(self, name: str) -> int:
        return self.ops.get(name, (0, 0.0))[0]

    def breakdown(self, n: int = 10) -> dict:
        top = sorted(self.ops.items(), key=lambda kv: -kv[1][1])[:n]
        gaps = sorted(self.idle.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v[1]] for k, v in top], "idle_gaps": [[k, v] for k, v in gaps]}


class _HostIndex:
    """Host events bucketed by millisecond, to find what overlaps a gap."""

    BIN = 1e6  # ns

    def __init__(self, events):
        self.events = events
        self.bins = defaultdict(list)
        for k, (a, b, _) in enumerate(events):
            for t in range(int(a // self.BIN), int(b // self.BIN) + 1):
                self.bins[t].append(k)

    def most_overlapping(self, lo: float, hi: float) -> str:
        best, best_overlap = "no host event", 0.0
        seen = set()
        for t in range(int(lo // self.BIN), int(hi // self.BIN) + 1):
            for k in self.bins.get(t, ()):
                if k in seen:
                    continue
                seen.add(k)
                a, b, name = self.events[k]
                ov = min(b, hi) - max(a, lo)
                if ov > best_overlap:
                    best, best_overlap = name, ov
        return best


def _events(line):
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name) for e in line.events]


def reduce_file(path: str, window: Optional[str]) -> Summary:
    """Reduce one ``.xplane.pb`` file (or its gzip, ``.xplane.pb.gz``)."""
    from jax.profiler import ProfileData

    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        return reduce_data(ProfileData.from_serialized_xspace(f.read()), window)


def reduce_data(pd, window: Optional[str]) -> Summary:
    devices = [p for p in pd.planes if re.fullmatch(r"/device:TPU:\d+", p.name)]
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    host = [p for p in pd.planes if p.name.startswith("/host:")]
    threads = [ln for p in host for ln in p.lines if ln.name.startswith("python")]
    lo = hi = None
    if window is not None:
        marks = [(a, b) for ln in threads for a, b, name in _events(ln) if name == window]
        if marks:
            lo, hi = min(a for a, _ in marks), max(b for _, b in marks)
    per_dev = []
    for p in devices:
        lines = {ln.name: _events(ln) for ln in p.lines}
        spans = [(a, b) for name in BUSY_LINES for a, b, _ in lines.get(name, [])]
        per_dev.append((lines, spans))
    if lo is None:
        every = [s for _, spans in per_dev for s in spans]
        if not every:
            raise ValueError("the trace holds no device operation")
        lo, hi = min(a for a, _ in every), max(b for _, b in every)
    ops: Dict[str, List] = defaultdict(lambda: [0, 0.0])
    busy_total = 0.0
    idle: Dict[str, float] = defaultdict(float)
    host = _HostIndex([(a, b, n) for ln in threads for a, b, n in _events(ln) if n != window])
    for lines, spans in per_dev:
        busy = union(clip(spans, lo, hi))
        busy_total += sum(b - a for a, b in busy)
        for a, b, name in lines.get(OP_LINE, []):
            if b > lo and a < hi:
                o = ops[op_name(name)]
                o[0] += 1
                o[1] += (min(b, hi) - max(a, lo)) * 1e-9
        for ga, gb in gaps_between(busy, lo, hi):
            idle[host.most_overlapping(ga, gb)] += (gb - ga) * 1e-9 / len(per_dev)
    return Summary(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy_total * 1e-9 / len(per_dev),
        ops={k: (v[0], v[1]) for k, v in ops.items()},
        idle=dict(idle),
    )


def reduce(log_dir: str, window: Optional[str]) -> Summary:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one .xplane.pb under {log_dir}, found {len(paths)}")
    return reduce_file(paths[0], window)
