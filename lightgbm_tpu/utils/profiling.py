"""Device-profiler captures.  The reference's compile-time TIMETAG phase
timers (serial_tree_learner.cpp:10-37, gbdt.cpp:22-63) have no counterpart
here: the phases of an iteration are spans of the structured tracer
(obs/trace.py; ``boosting``, ``bagging``, ``tree``, ``train_score``,
``valid_score`` in boosting/gbdt.py), and an enabled span is also a
``jax.profiler.TraceAnnotation("lgbm:<name>")``, which is what puts a host
phase on a profiler trace's clock.  (A ``jax.named_scope`` there never
did: a scope names operations while a program is TRACED, and these phases
wrap calls to programs that are already compiled.  The scopes that do
reach the compiled programs sit inside them, obs/phases.py.)
"""

from __future__ import annotations

import contextlib
import os
import time
from typing import Iterator

import jax

from ..obs.trace import tracer
from .log import Log


@contextlib.contextmanager
def profile_trace(log_dir: str) -> Iterator[None]:
    """Device-level profiler trace (the deep-dive tool the reference never
    had): view with TensorBoard / xprof."""
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


class XprofCapture:
    """Bounded-iteration device-profiler capture — the prewired harness
    behind ``LIGHTGBM_TPU_XPROF=<dir>``.

    Skips the first ``LIGHTGBM_TPU_XPROF_SKIP`` iterations (default 1:
    compiles and warmup would drown the steady-state timeline), then
    runs :func:`profile_trace` across the next
    ``LIGHTGBM_TPU_XPROF_ITERS`` iterations (default 4) and stops — one
    bounded xplane capture per run.  With ``LIGHTGBM_TPU_TRACE`` set as
    well, every tracer span lands in the capture as an ``lgbm:<name>``
    annotation, so the call sites need no further instrumentation: drive
    ``on_iter_start()`` / ``on_iter_end()`` around each training
    iteration and call :meth:`close` on the way out (stops a capture
    the run abandoned mid-window)."""

    def __init__(self, log_dir: str, skip: int = None, iters: int = None):
        self.log_dir = log_dir
        self.skip = int(os.environ.get("LIGHTGBM_TPU_XPROF_SKIP", "1")) \
            if skip is None else int(skip)
        self.iters = max(1, int(
            os.environ.get("LIGHTGBM_TPU_XPROF_ITERS", "4"))
            if iters is None else int(iters))
        self._seen = 0
        self._active = False
        self._done = False
        self._t0 = 0.0

    def on_iter_start(self) -> None:
        if self._done or self._active or self._seen < self.skip:
            return
        jax.profiler.start_trace(self.log_dir)
        self._active = True
        self._t0 = time.perf_counter()
        Log.info("xprof capture started -> %s (iters %d..%d)",
                 self.log_dir, self._seen, self._seen + self.iters - 1)

    def on_iter_end(self) -> None:
        self._seen += 1
        if self._active and self._seen >= self.skip + self.iters:
            self._stop()

    def close(self) -> None:
        """Stop an in-flight capture (early exit / exception path)."""
        if self._active:
            self._stop()

    def _stop(self) -> None:
        wall = time.perf_counter() - self._t0
        try:
            jax.profiler.stop_trace()
        finally:
            self._active = False
            self._done = True
        tracer.event("xprof.capture", dir=self.log_dir,
                     iters=self.iters, skip=self.skip,
                     wall_s=round(wall, 6))
        Log.info("xprof capture done: %d iteration(s) in %.3f s -> %s",
                 self.iters, wall, self.log_dir)


def maybe_xprof_capture() -> "XprofCapture | None":
    """The env-gated constructor training entry points call:
    ``LIGHTGBM_TPU_XPROF=<dir>`` arms a capture, unset returns None."""
    log_dir = os.environ.get("LIGHTGBM_TPU_XPROF", "").strip()
    return XprofCapture(log_dir) if log_dir else None
