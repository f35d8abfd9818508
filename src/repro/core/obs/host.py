"""Wall-clock host spans on the profiler's clock.

The spans of :mod:`repro.core.obs.trace` run on the simulation clock and
are deterministic by design: they are carbon-attribution records, not
timings. This module is the wall-clock side. ``span(name, **args)``
opens a ``jax.profiler.TraceAnnotation``, so a profiler session
(``jax.profiler.trace`` / ``start_trace``) records each layer's host time
on the host plane of its trace, on the same clock as the device's ops::

    with span("admit.cells") as sp:
        cells = build()
        sp.set_metadata(cells=len(cells))

Without a session a span is one no-op enter and exit (about 1 µs). A
process that never imported jax cannot hold a profiler session, so there
``span`` returns a shared no-op context and imports nothing: numpy-only
installs and worker processes stay free of jax.

Args are ints or short strings that already exist at the call site.
Host spans never enter :class:`~repro.core.obs.trace.Span`, fleet
reports or checkpoints; the profiler keeps them in memory and writes
them out when its session stops. The span names and the nesting are
listed in ``docs/observability.md``.
"""
from __future__ import annotations

import sys


class _NoSpan:
    """The span of a process without jax: enter, exit and metadata do
    nothing."""
    __slots__ = ()

    def __enter__(self) -> "_NoSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set_metadata(self, **args) -> None:
        pass


_NO_SPAN = _NoSpan()
_annotation = None                     # jax.profiler.TraceAnnotation


def span(name: str, **args):
    """A context that records ``name`` with ``args`` as one host event of
    an active profiler session. The event starts when the span is made,
    so make it in the ``with`` statement that enters it. Args known only
    inside the span go in through ``set_metadata``."""
    global _annotation
    if _annotation is None:
        if "jax" not in sys.modules:
            return _NO_SPAN
        try:
            from jax.profiler import TraceAnnotation
        except ImportError:
            return _NO_SPAN
        _annotation = TraceAnnotation
    return _annotation(name, **args)
