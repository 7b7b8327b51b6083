"""Spans around the program's pipeline stages, from the benchmark's side.

:func:`spanned` wraps each stage of a ``stages_for`` chain in a thin
object that forwards everything to the stage and records a host span
(``bench:stage:<name>``) around each call into it, on the profiler's
clock (``jax.profiler.TraceAnnotation``) and in :class:`Spans`.  In the
traced run the wrapper also blocks on the stage's outputs before closing
the span, so the span holds the device work it launched; the untimed
cost of that blocking is why only the traced run wraps.
"""
from __future__ import annotations

import time

import jax


class Spans:
    """Host spans and counters of one run: [(name, t0, t1)] on
    ``time.perf_counter`` and a name -> number dict."""

    def __init__(self):
        self.spans: list[tuple[str, float, float]] = []
        self.counters: dict[str, float] = {}

    def span(self, name: str):
        return _Span(self, name)

    def total(self, name: str, within=None) -> float:
        """Seconds in spans called ``name``; with ``within`` (t0, t1),
        only those that started inside it."""
        lo, hi = within or (float("-inf"), float("inf"))
        return sum(t1 - t0 for n, t0, t1 in self.spans
                   if n == name and lo <= t0 <= hi)


class _Span:
    def __init__(self, spans: Spans, name: str):
        self.spans = spans
        self.name = name
        self.ann = jax.profiler.TraceAnnotation("bench:" + name)

    def __enter__(self):
        self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.spans.spans.append((self.name, self.t0, time.perf_counter()))
        self.ann.__exit__(*exc)


class _SpannedStage:
    """Forwards attribute reads to ``inner``; wraps its calls in spans."""

    _CALLS = ("run", "num_units", "init_state", "run_segment", "finalize")

    def __init__(self, inner, spans: Spans):
        self._inner = inner
        self._spans = spans

    def __getattr__(self, attr):
        value = getattr(self._inner, attr)
        if attr not in self._CALLS:
            return value

        def call(*args, **kwargs):
            with self._spans.span("stage:" + self._inner.name):
                out = value(*args, **kwargs)
                return jax.block_until_ready(out)

        return call


def spanned(stages, spans: Spans) -> list:
    return [_SpannedStage(s, spans) for s in stages]
