"""Named host spans and counters of a pool's tick path.

``with spans.span(name):`` enters a ``jax.profiler.TraceAnnotation`` of
that name, so a profiler trace shows the span on the device trace's
clock, and adds the block's wall milliseconds to ``ms[name]``.  Nested
spans take dotted names under their parent (``transport.admit`` inside
``transport``), so a parent's time includes its children's.
``count(name, n)`` adds ``n`` to ``counts[name]``: bytes moved between
host and device, static rebuilds, breaks replayed and the programs
that replay them.

A ``TraceAnnotation`` with no profiler session open records nothing, so
a span costs the same traced or not, apart from the trace itself.
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, Iterator

import numpy as np
from jax.profiler import TraceAnnotation


class Spans:
    """Accumulates span milliseconds into ``ms`` and counters into
    ``counts`` (dicts the owner exposes, e.g. ``ClientPool.phase_ms``)."""

    def __init__(self, ms: Dict[str, float], counts: Dict[str, int]):
        self.ms = ms
        self.counts = counts

    @contextlib.contextmanager
    def span(self, name: str) -> Iterator[None]:
        t0 = time.perf_counter()
        try:
            with TraceAnnotation(name):
                yield
        finally:
            self.ms[name] = self.ms.get(name, 0.0) \
                + (time.perf_counter() - t0) * 1e3

    def count(self, name: str, n: int) -> None:
        self.counts[name] = self.counts.get(name, 0) + int(n)

    def h2d(self, *arrays) -> None:
        """Count the bytes of the host arrays among ``arrays`` (device
        arrays and Python scalars are left out) under ``h2d_bytes``."""
        self.count("h2d_bytes", sum(
            a.nbytes for a in arrays if isinstance(a, (np.ndarray,
                                                       np.generic))))

    def d2h(self, arr) -> np.ndarray:
        """``np.asarray(arr)``, its bytes counted under ``d2h_bytes``."""
        out = np.asarray(arr)
        self.count("d2h_bytes", out.nbytes)
        return out
