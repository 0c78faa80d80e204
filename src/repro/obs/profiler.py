"""``jax.profiler`` integration: host-span annotations + trace capture.

``annotate(name)`` wraps a host-side region in a
``jax.profiler.TraceAnnotation`` so device profiles (captured with
``device_trace`` / ``make profile``) line up with the serve path's own
span names — the kernel dispatch sites in ``repro.search.substrate`` use
``rnsg.scan_dispatch`` / ``rnsg.beam_dispatch`` / ``rnsg.gather`` style
names.  When no profiler session is active a ``TraceAnnotation`` is a few
nanoseconds of overhead, so the annotations stay on unconditionally.
"""
from __future__ import annotations

from contextlib import contextmanager

import jax.profiler


def annotate(name: str):
    """Context manager marking a host region in the profiler timeline."""
    return jax.profiler.TraceAnnotation(name)


@contextmanager
def device_trace(log_dir: str):
    """Capture a ``jax.profiler`` trace (TensorBoard format) around a
    block (``make profile`` / ``tools/profile_capture.py``)."""
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
