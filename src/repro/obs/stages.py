"""``stage``: the one span primitive of the serve path.

A stage is a named stretch of host work on one thread.  Entering one does
three things:

* it opens ``jax.profiler.TraceAnnotation("rnsg.<name>")``, so the stage
  lies on the device trace's clock beside the device operations (with no
  profiler session active it only asks whether one is);
* on exit it adds its wall time (``perf_counter_ns``) to the registry
  histogram ``stage_<name>_ms`` — always, with the profiler on or off —
  when a registry is given;
* when a ``QueryTrace`` rides the request, it appends the same span there.

Stages nest.  Each stage adds its wall time to the stage open around it on
the same thread, so ``self_ms`` (wall less the stages nested inside) is
exact; the serve path's dispatcher tiles its loop with leaf stages
(``DISPATCHER_STAGES``), so there wall and self time agree.

``set_batch(seq)`` tags every stage the calling thread opens afterwards
with ``batch=<seq>`` in its profiler annotation: the engine sets it per
batch on both of its threads, so the spans of one batch share an
identifier in the trace.
"""
from __future__ import annotations

import threading
from time import perf_counter_ns
from typing import Optional

from jax.profiler import TraceAnnotation

from repro.obs.trace import Span

PREFIX = "rnsg."

#: the leaf stages that tile the engine's dispatcher thread, from waiting
#: for a resolved batch to setting its last future (``serving/engine.py``,
#: the single-chip substrate and the mesh substrate, ``search/substrate.py``,
#: and the distributed local path's ``merge``)
DISPATCHER_STAGES = ("await_batch", "plan", "scan_prep", "scan_dispatch",
                     "rerank", "scan_block", "beam_prep", "beam_dispatch",
                     "graph_beam_dispatch", "beam_block", "mesh_prep",
                     "mesh_dispatch", "mesh_block", "merge", "assemble",
                     "complete")


class _Local(threading.local):
    def __init__(self):
        self.batch: Optional[int] = None
        self.stack: list = []       # the stages open on this thread


_local = _Local()
_NAMES = {}     # stage name -> (profiler label, histogram name)


def set_batch(seq: Optional[int]) -> None:
    """Tag the calling thread's later stages with ``batch=<seq>`` (``None``
    clears the tag)."""
    _local.batch = seq


def _names(name: str):
    """(profiler label, registry histogram) of a stage."""
    out = _NAMES.get(name)
    if out is None:
        out = _NAMES[name] = (PREFIX + name, f"stage_{name}_ms")
    return out


class _NullAttrs(dict):
    """Attribute sink for stages with no trace: writes are dropped, so call
    sites stay branch-free."""

    def __setitem__(self, k, v):
        pass

    def update(self, *a, **kw):
        pass


_NULL_ATTRS = _NullAttrs()


class stage:
    """``with stage(name, registry, trace, **meta) as st:`` — see the module
    docstring.  ``meta`` goes to the profiler annotation and, with a trace,
    to the span's attributes; ``st.attrs`` takes more attributes found while
    the stage runs (dropped without a trace).  After exit ``st.ms`` is the
    wall time and ``st.self_ms`` the wall less nested stages, in ms."""
    __slots__ = ("name", "attrs", "ms", "self_ms", "_reg", "_trace", "_meta",
                 "_ann", "_t0", "_child")

    def __init__(self, name: str, registry=None, trace=None, **meta):
        # the wall starts here, so a stage's own set-up is inside it and
        # consecutive stages on a thread leave almost no gap between them
        self._t0 = perf_counter_ns()
        self.name = name
        self._reg = registry
        self._trace = trace
        self._meta = meta
        self.attrs = dict(meta) if trace is not None else _NULL_ATTRS
        self._child = 0

    def __enter__(self) -> "stage":
        loc = _local
        # an annotation opened with no profiler session records nothing,
        # so none is built then (the annotation's own test, made first)
        if TraceAnnotation.is_enabled():
            meta = self._meta
            if loc.batch is not None:
                meta["batch"] = loc.batch
            self._ann = TraceAnnotation(_names(self.name)[0], **meta)
            self._ann.__enter__()
        else:
            self._ann = None
        loc.stack.append(self)
        return self

    def __exit__(self, *exc) -> bool:
        t1 = perf_counter_ns()
        wall = t1 - self._t0
        stack = _local.stack
        stack.pop()
        if stack:
            stack[-1]._child += wall
        self.ms = ms = wall * 1e-6
        self.self_ms = (wall - self._child) * 1e-6
        if self._reg is not None:
            self._reg.histogram(_names(self.name)[1]).observe(ms)
        if self._trace is not None:
            self._trace.spans.append(Span(self.name, self._t0 * 1e-9,
                                          t1 * 1e-9, self.attrs,
                                          child_s=self._child * 1e-9))
        if self._ann is not None:
            self._ann.__exit__(*exc)
        return False
