"""Host spans the benchmark records around its calls into the program.

Each is a ``jax.profiler.TraceAnnotation`` named ``bench.<name>``: it
costs next to nothing while the profiler is off, and lands on the same
clock as the device's events in a traced run (``trace.py``).
"""
from __future__ import annotations

from chipbench.trace import SPAN_PREFIX


def span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)
