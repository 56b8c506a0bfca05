"""Named spans of the program's phases, on the profiler's clock.

:class:`span` enters a ``jax.profiler.TraceAnnotation`` while a profiler
trace is being collected: a host event that shares a clock with the
device's events, so a gap in which the device idles can be put down to
the phase the host was in.  With no trace running no annotation is made.
Given a dict, the span also adds its elapsed seconds to ``into[key]``: the
stats dicts and timers of each layer (build stats ``t_*_s``,
``engine_stats()``) are accumulated by the same spans the trace shows, so
the two never disagree on where a phase starts and ends.

The annotation touches no device, so a span may run on the pipeline's
worker thread too (the merge's refill prefetch reads chunks there).
"""
from __future__ import annotations

from time import perf_counter
from typing import Optional

from jax.profiler import TraceAnnotation

__all__ = ["span"]

_tracing = TraceAnnotation.is_enabled  # a profiler is collecting host events


class span:
    """``with span(name[, into, key]):`` — a host span named ``name``;
    with ``into``, its seconds are added to ``into[key]`` (started at 0).

    A class rather than a generator context manager, and no annotation
    while no trace runs: the serve path opens a span for every chunk-cache
    miss, a thousand a batch, so a span has to cost next to nothing.
    """

    __slots__ = ("_ann", "_into", "_key", "_t0")

    def __init__(self, name: str, into: Optional[dict] = None,
                 key: Optional[str] = None):
        self._ann = TraceAnnotation(name) if _tracing() else None
        self._into = into
        self._key = key

    def __enter__(self) -> "span":
        if self._ann is not None:
            self._ann.__enter__()
        if self._into is not None:
            self._t0 = perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        if self._into is not None:
            self._into[self._key] = (self._into.get(self._key, 0.0)
                                     + perf_counter() - self._t0)
        if self._ann is not None:
            self._ann.__exit__(*exc)
