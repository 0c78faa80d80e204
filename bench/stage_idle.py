"""Device idle put down to the serve path's stages.

Reads the ``.xplane.pb`` of a traced window (``trace_reduce.find_trace``)
and puts every nanosecond of the ``bench.window`` span in which no
operation ran on the device down to the program's ``rnsg.*`` stage that
was innermost on the engine's dispatcher thread at that instant — its self
time, so a stage nested in another takes its own share.  The dispatcher
thread is the host line holding the most ``rnsg.*dispatch`` spans.  Idle
under no stage is ``"no stage"``.

Idle while the dispatcher waits for a batch (``rnsg.await_batch``) is idle
for want of requests; every other idle nanosecond is host-bound: the chip
waited on host work.  A trace without ``rnsg.await_batch`` (a program
older than the stage spans) counts all of its idle as host-bound.
"""
from __future__ import annotations

from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from bench import trace_reduce

STAGE_PREFIX = "rnsg."
WAIT_STAGE = "rnsg.await_batch"
NO_STAGE = "no stage"

Span = Tuple[float, float, str]


@dataclass
class StageIdle:
    window_s: float
    idle_s: Dict[str, float] = field(default_factory=dict)   # by stage

    @property
    def host_bound_s(self) -> float:
        return sum(v for k, v in self.idle_s.items() if k != WAIT_STAGE)

    @property
    def host_bound_share(self) -> float:
        return self.host_bound_s / self.window_s


def innermost(spans: Sequence[Span]) -> List[Span]:
    """Spans of one thread (properly nested) -> disjoint segments, each
    labelled with the span innermost over it, in time order."""
    out: List[Span] = []
    stack: List[Tuple[float, str]] = []         # (end, name), outer first
    cur = 0.0

    def emit(a, b, name):
        if b > a:
            out.append((a, b, name))
    for s, e, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, outer = stack.pop()
            emit(cur, end, outer)
            cur = end
        if stack:
            emit(cur, s, stack[-1][1])
            e = min(e, stack[-1][0])
        stack.append((e, name))
        cur = s
    while stack:
        end, name = stack.pop()
        emit(cur, end, name)
        cur = end
    return out


def attribute(idle: Sequence[Tuple[float, float]],
              segments: Sequence[Span]) -> Dict[str, float]:
    """Sum each idle interval's overlap with the labelled segments (both
    sorted and disjoint); what no segment covers goes to ``NO_STAGE``."""
    total: Dict[str, float] = defaultdict(float)
    j = 0
    for a, b in idle:
        covered = 0.0
        while j < len(segments) and segments[j][1] <= a:
            j += 1
        i = j
        while i < len(segments) and segments[i][0] < b:
            s, e, name = segments[i]
            ov = min(b, e) - max(a, s)
            if ov > 0:
                total[name] += ov
                covered += ov
            i += 1
        if b - a > covered:
            total[NO_STAGE] += b - a - covered
    return dict(total)


def dispatcher_line(lines: Sequence[Sequence[Span]]) -> Optional[int]:
    """Index of the host line with the most ``rnsg.*dispatch`` spans."""
    counts = [sum(1 for _, _, n in line if n.startswith(STAGE_PREFIX)
                  and n.endswith("dispatch")) for line in lines]
    if not counts or max(counts) == 0:
        return None
    return counts.index(max(counts))


def reduce(window: Tuple[float, float], busy: Sequence[Tuple[float, float]],
           lines: Sequence[Sequence[Span]]) -> Optional[StageIdle]:
    """Idle of ``window`` (device ``busy`` intervals, any order) by the
    innermost stage on the dispatcher line among host ``lines``; ``None``
    when no line holds a dispatch span.  Times in ns, results in s."""
    k = dispatcher_line(lines)
    if k is None:
        return None
    lo, hi = window
    idle = trace_reduce.gaps(trace_reduce.clip(trace_reduce.union(
        list(busy)), lo, hi), lo, hi)
    stages = [sp for sp in lines[k] if sp[2].startswith(STAGE_PREFIX)]
    by = attribute(idle, innermost(stages))
    return StageIdle(window_s=(hi - lo) * 1e-9,
                     idle_s={n: v * 1e-9 for n, v in by.items()})


def read(path) -> Optional[StageIdle]:
    """``reduce`` over a recorded trace: the ``bench.window`` span, the
    first device's op intervals, and every host thread's spans."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    window, busy, lines = None, None, []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans = []
                for ev in line.events:
                    end = ev.start_ns + ev.duration_ns
                    if ev.name == trace_reduce.WINDOW_SPAN:
                        window = (ev.start_ns, end)
                    elif ev.name.startswith(STAGE_PREFIX):
                        spans.append((ev.start_ns, end, ev.name))
                lines.append(spans)
        elif (busy is None and plane.name.startswith("/device:")
              and "CPU" not in plane.name):
            ops = [(ev.start_ns, ev.start_ns + ev.duration_ns)
                   for line in plane.lines
                   if line.name == trace_reduce.OPS_LINE
                   for ev in line.events]
            busy = ops or None
    if window is None or busy is None:
        return None
    return reduce(window, busy, lines)


def log_idle(res: StageIdle) -> None:
    """Idle seconds by stage on stderr, largest first."""
    from bench import harness
    idle = sum(res.idle_s.values())
    parts = ", ".join(f"{n} {v:.6f}s" for n, v in
                      sorted(res.idle_s.items(), key=lambda t: -t[1]))
    harness.log(f"stage idle: {idle:.6f}s of {res.window_s:.6f}s by "
                f"dispatcher stage: {parts}; host-bound "
                f"{res.host_bound_s:.6f}s")
