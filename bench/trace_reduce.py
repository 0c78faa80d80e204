"""Reduce a ``jax.profiler`` trace (``.xplane.pb``) to the benchmark's
device numbers.

* busy — the union of the intervals in which an operation ran on a device
  (events of the device planes' ``XLA Ops`` lines), clipped to the
  measured window and averaged over the devices;
* window — the host span the harness marks with ``WINDOW_SPAN``;
* kernel time — the summed device durations of one program's executions
  (events of the ``XLA Modules`` line named ``jit_<function>``);
* top device ops — each operation's own time (a ``while`` loop's time
  less the body ops it encloses), summed by name;
* idle gaps — the stretches of the window in which no operation ran on the
  device, each named after the host span (``rnsg.*`` annotations and the
  harness's own) that overlapped it most.

Only ``jax.profiler.ProfileData`` is needed to read the file.
"""
from __future__ import annotations

import bisect
import glob
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
HOST_PREFIXES = ("rnsg.", "bench.")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

Interval = Tuple[float, float]


@dataclass
class Reduction:
    window_s: float
    busy_s: float                       # mean over devices
    devices: int
    module_s: Dict[str, float] = field(default_factory=dict)
    top_ops: List[Tuple[str, float]] = field(default_factory=list)
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def kernel_s(self, module: str) -> Optional[float]:
        """Device seconds of one program (``jit_<function>``), or ``None``
        when the window never ran it."""
        return self.module_s.get(module)


def find_trace(log_dir) -> Path:
    hits = sorted(glob.glob(str(Path(log_dir) / "**" / "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return Path(hits[-1])


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def _short(op: str) -> str:
    """``%fusion.3 = f32[8,128]{...} fusion(...)`` -> ``%fusion.3 f32[8,128]``."""
    lhs, _, rhs = op.partition(" = ")
    return f"{lhs} {rhs.split('{')[0].split(' ')[0]}".strip()


def _module_of(name: str) -> str:
    """``jit_foo(123)`` -> ``jit_foo``."""
    return name.split("(")[0].strip()


def reduce_trace(path, top: int = 10) -> Reduction:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    window: Optional[Interval] = None
    host: List[Tuple[float, float, str]] = []
    devices = []
    for plane in pd.planes:
        name = plane.name
        if name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_SPAN:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    elif ev.name.startswith(HOST_PREFIXES):
                        host.append((ev.start_ns,
                                     ev.start_ns + ev.duration_ns, ev.name))
        elif name.startswith("/device:") and "CPU" not in name:
            ops, mods = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((ev.start_ns, ev.duration_ns, ev.name)
                               for ev in line.events)
                elif line.name == MODULES_LINE:
                    mods.extend((ev.start_ns, ev.duration_ns,
                                 _module_of(ev.name)) for ev in line.events)
            if ops:
                devices.append((ops, mods))
    if window is None:
        raise ValueError(f"{path}: no {WINDOW_SPAN!r} span in the trace")
    if not devices:
        raise ValueError(f"{path}: no device operations in the trace")
    lo, hi = window
    busy_ns = 0.0
    mod_ns, op_ns = defaultdict(float), defaultdict(float)
    first_busy = None
    for ops, mods in devices:
        iv = clip(union([(s, s + d) for s, d, _ in ops]), lo, hi)
        busy_ns += sum(b - a for a, b in iv)
        if first_busy is None:
            first_busy = iv
        for s, d, mod in mods:
            a, b = max(s, lo), min(s + d, hi)
            if b > a:
                mod_ns[mod] += b - a
        names = _modules_at(ops, mods)
        for (s, e, op), mod in zip(_self_times(ops, lo, hi), names):
            op = _short(op)
            op_ns[f"{mod}/{op}" if mod else op] += e
    nd = len(devices)
    top_ops = sorted(op_ns.items(), key=lambda t: -t[1])[:top]
    idle = _attribute(gaps(first_busy, lo, hi), host)
    return Reduction(
        window_s=(hi - lo) * 1e-9, busy_s=busy_ns / nd * 1e-9, devices=nd,
        module_s={m: v / nd * 1e-9 for m, v in mod_ns.items()},
        top_ops=[(n, v / nd * 1e-9) for n, v in top_ops],
        idle_gaps=[(n, v * 1e-9) for n, v in idle[:top]])


def _self_times(ops, lo: float, hi: float):
    """(start, self ns inside the window, name) of each op, in start order:
    a control-flow op (``while``, ``conditional``) encloses the ops of its
    body on the same line, and only the time no enclosed op covers is its
    own."""
    ops = sorted(ops, key=lambda o: (o[0], -o[1]))
    own = [min(s + d, hi) - max(s, lo) for s, d, _ in ops]
    own = [max(v, 0.0) for v in own]
    stack: List[int] = []
    for i, (s, d, _) in enumerate(ops):
        while stack and ops[stack[-1]][0] + ops[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            p = stack[-1]
            own[p] -= max(min(s + d, hi) - max(s, lo), 0.0)
        stack.append(i)
    return [(ops[i][0], max(own[i], 0.0), ops[i][2])
            for i in range(len(ops))]


def _modules_at(ops, mods) -> List[str]:
    """The module whose execution span holds each op's start (ops in the
    order ``_self_times`` returns them)."""
    ops = sorted(ops, key=lambda o: (o[0], -o[1]))
    mods = sorted(mods)
    out, j = [], 0
    for s, _, _ in ops:
        while j < len(mods) and mods[j][0] + mods[j][1] < s:
            j += 1
        out.append(mods[j][2] if j < len(mods) and mods[j][0] <= s else "")
    return out


def _attribute(idle: List[Interval], host) -> List[Tuple[str, float]]:
    """Idle time summed by the host span that overlapped each gap most
    (``"no host span"`` when none did; the shorter span wins a tie),
    largest first."""
    host = sorted(host)
    starts = [s for s, _, _ in host]
    longest = max((e - s for s, e, _ in host), default=0.0)
    total: Dict[str, float] = defaultdict(float)
    for a, b in idle:
        best, best_ov, best_len = "no host span", 0.0, float("inf")
        i0 = bisect.bisect_left(starts, a - longest)
        i1 = bisect.bisect_left(starts, b)
        for s, e, name in host[i0:i1]:
            ov = min(b, e) - max(a, s)
            if ov > best_ov or (ov == best_ov and ov > 0
                                and e - s < best_len):
                best, best_ov, best_len = name, ov, e - s
        total[best] += b - a
    return sorted(total.items(), key=lambda t: -t[1])
