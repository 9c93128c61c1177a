"""Host spans: ``span(name, into)`` times one stage of host work.

A span is a ``jax.profiler.TraceAnnotation``, so a profile shows it on
its host plane, on the same clock as the device's ops; and it adds its
``time.perf_counter`` seconds to ``into``, so the seconds are counted in
every call, with or without the profiler. The key in ``into`` is the
name's last dotted part (``sweep.lower`` adds to ``into["lower"]``), and
a stage entered several times in one call sums its spans.
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, Iterator

import jax


@contextlib.contextmanager
def span(name: str, into: Dict[str, float]) -> Iterator[None]:
    key = name.rpartition(".")[2]
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(name):
            yield
    finally:
        into[key] = into.get(key, 0.0) + time.perf_counter() - t0
