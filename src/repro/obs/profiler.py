"""``jax.profiler`` trace capture.

Host spans on the profiler's clock come from ``repro.obs.stage`` (every
stage opens an ``rnsg.<name>`` annotation); ``device_trace`` captures a
session around a block so those spans line up with the device's ops.
"""
from __future__ import annotations

from contextlib import contextmanager

import jax.profiler


@contextmanager
def device_trace(log_dir: str):
    """Capture a ``jax.profiler`` trace (TensorBoard format) around a
    block (``make profile`` / ``tools/profile_capture.py``)."""
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
