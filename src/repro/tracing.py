"""Program spans and counters, recorded only while ``jax.profiler`` collects.

A span is a ``jax.profiler.TraceAnnotation`` named ``repro.<name>``: its
events land in the profiler's ``.xplane.pb`` on the same clock as the
device's op and module events, so a device-idle stretch can be put down
to the host step running at that instant.  While no profiler collects, a
span costs about a microsecond and records nothing.

Counters ride on a span as TraceMe metadata (``set_metadata``), and come
back as the event's stats.  Compute them only when ``enabled()``, so they
cost nothing while tracing is off.

Spans mark phases of one self-scheduled loop (DESIGN.md Sec. 14), never
one claim or tile, so their count per loop does not grow with N.
"""
from __future__ import annotations

import contextlib
import sys

PREFIX = "repro."


def span(name: str, **counters):
    """A host span ``repro.<name>``, with ``counters`` as its metadata.

    A process that has not imported JAX runs no profiler: there the span
    is a null context, and JAX stays unimported (``repro.dls`` runs its
    host runtimes without it).
    """
    jax = sys.modules.get("jax")
    if jax is None:
        return contextlib.nullcontext()
    return jax.profiler.TraceAnnotation(PREFIX + name, **counters)


def enabled() -> bool:
    """Whether a profiler is collecting, so spans and counters record."""
    jax = sys.modules.get("jax")
    return jax is not None and jax.profiler.TraceAnnotation.is_enabled()


class launch:
    """The span ``repro.<name>`` around one dispatch of the jitted ``fn``.

    Its counter ``compiled`` is 1 when the dispatch grew ``fn``'s cache of
    compiled signatures (a trace and a compile, or a load from the
    persistent cache, on the served path), else 0.
    """

    __slots__ = ("_fn", "_span", "_size")

    def __init__(self, name: str, fn):
        self._fn, self._span = fn, span(name)

    def __enter__(self):
        self._span.__enter__()
        self._size = self._fn._cache_size() if enabled() else None
        return self

    def __exit__(self, *exc):
        if self._size is not None:
            self._span.set_metadata(
                compiled=int(self._fn._cache_size() > self._size))
        return self._span.__exit__(*exc)
