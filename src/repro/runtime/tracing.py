"""Host spans on the profiler's clock, with their seconds kept by the owner.

``span(name, into, **meta)`` opens a ``jax.profiler.TraceAnnotation`` (an
event on the ``/host:CPU`` plane of a trace, with ``meta`` as its stats) and
adds the span's ``perf_counter`` duration to ``into[name]``, so an untraced
run still knows where its host time went.  When no trace is being taken
the annotation formats nothing: a span costs two clock reads and a dict add.

Metadata known only inside the span goes on with ``note(**meta)``::

    with span("engine.prefill", self.phase_s) as sp:
        ...
        sp.note(valid_tokens=n)
"""

from __future__ import annotations

import time

from jax.profiler import TraceAnnotation

__all__ = ["span"]


class span:
    __slots__ = ("name", "_into", "_ann", "_t0")

    def __init__(self, name: str, into: dict | None = None, **meta):
        self.name = name
        self._into = into
        self._ann = TraceAnnotation(name, **meta)

    def __enter__(self):
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def note(self, **meta):
        """Add metadata to the span's trace event (nothing when untraced)."""
        self._ann.set_metadata(**meta)

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        if self._into is not None:
            self._into[self.name] = self._into.get(self.name, 0.0) + dt
        return False
