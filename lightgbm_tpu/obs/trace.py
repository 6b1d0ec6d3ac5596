"""Structured run tracer — the observability core.

The reference ships compile-time TIMETAG phase timers
(serial_tree_learner.cpp:10-37, gbdt.cpp:22-63) whose only sink is a
destructor printf.  This tracer is the TPU-era replacement: nested
host-side spans, counters and gauges written as one-record-per-line JSON
(JSONL) so a failed run still leaves every record flushed before death,
plus per-iteration summary records that the
``python -m lightgbm_tpu report`` CLI aggregates.

Enable with ``LIGHTGBM_TPU_TRACE=/path/to/trace.jsonl`` (re-read at every
``engine.train``/``GBDT.init``) or programmatically via
``tracer.configure(path)``.  Disabled mode is near-free: ``span()``
returns a shared no-op context manager and every other entry point is a
single attribute check.

Record schema (all records carry ``ev`` and ``ts`` = time.time()):

  {"ev":"meta", "version":1, "pid":..., "argv":[...]}
  {"ev":"span", "name":..., "dur_s":..., "depth":..., "parent":..., ...attrs}
  {"ev":"counter"|"gauge", "name":..., "value":..., ...attrs}
  {"ev":"event", "name":..., ...attrs}
  {"ev":"program", "name":..., "module":..., "matrix":..., "ops":{instr: phase}}
  {"ev":"iter", "iter":i, "wall_s":..., "phases":{name: secs},
   "compiles":n, "host_rss_mb":..., "dev_mb":..., ...fields}

Spans opened while an iteration record is open additionally accumulate
into that iteration's ``phases`` map — that is how the per-phase
histogram/split/partition breakdown lands on each ``iter`` record.

A **stage** (``tracer.stage``) is a span that is also KEPT, in
``tracer.stages``, whether or not a sink is configured: set-up happens
before anybody switches a sink on, and it is measured from inside all the
same.  With the sink off a stage costs two clock reads and one dict.  The
rule that keeps it off the hot path: a stage is opened once a process, a
``Dataset``, a ``Booster`` or a compiled program, never once an iteration
or a chunk (docs/OBSERVABILITY.md lists the vocabulary).
"""

from __future__ import annotations

import atexit
import collections
import contextlib
import json
import os
import sys
import threading
import time
from typing import Any, Dict, Optional


class _NullSpan:
    """Shared no-op context manager returned when tracing is disabled."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()
# the program's spans on a profiler trace: "lgbm:<span name>"
ANNOTATION_PREFIX = "lgbm:"
# tracer.stages is bounded: a process that builds boosters for days keeps
# the newest STAGES_MAX stages
STAGES_MAX = 512


def _max_bytes_from_env() -> int:
    """LIGHTGBM_TPU_TRACE_MAX_MB as a byte cap (0/unset/garbage = no
    rotation — the historical unbounded behavior)."""
    raw = os.environ.get("LIGHTGBM_TPU_TRACE_MAX_MB", "").strip()
    if not raw:
        return 0
    try:
        mb = float(raw)
    except ValueError:
        return 0
    return int(mb * 1024 * 1024) if mb > 0 else 0


def _flight_recorder():
    """Lazy accessor for the crash flight recorder (obs/flight.py) —
    imported on first enabled-mode emit, cached after."""
    global _FLIGHT
    if _FLIGHT is None:
        from . import flight

        _FLIGHT = flight.recorder
    return _FLIGHT


_FLIGHT = None


class _Span:
    """An enabled span, or a stage.  Besides its JSONL record an enabled
    span is a ``jax.profiler.TraceAnnotation("lgbm:<name>")``: under a
    profiler session the span lands on the device trace's own clock, so a
    reader of the trace needs no wall-clock bridge to lay the program's host
    spans over the device's idle gaps.  (Without a session the annotation is
    a flag check.)  A stage (``keep``) is opened with the sink off too, and
    then is two clock reads and an entry in ``tracer.stages``: it closes by
    the state it opened in (no annotation to exit where none was entered)
    and its record goes where the sink is by then, because ``GBDT.init``
    re-reads the environment inside ``booster_init``."""

    __slots__ = ("_tr", "name", "attrs", "_t0", "_ann", "_keep")

    def __init__(self, tr: "Tracer", name: str, attrs: Dict[str, Any], keep: bool = False):
        self._tr = tr
        self.name = name
        self.attrs = attrs
        self._keep = keep

    def __enter__(self):
        self._ann = None
        if self._tr.enabled:
            import jax

            self._ann = jax.profiler.TraceAnnotation(ANNOTATION_PREFIX + self.name)
            self._ann.__enter__()
        self._tr._stack.append(self.name)
        if self._keep:
            self._tr._stage_stack().append(self.name)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        tr = self._tr
        for stack in (tr._stack, tr._stage_stack()) if self._keep else (tr._stack,):
            if stack and stack[-1] is self.name:
                stack.pop()
        tr._close_span(self.name, self._t0, dur, self.attrs, self._keep)
        return False


class Tracer:
    """Process-global structured tracer with a JSONL sink."""

    def __init__(self):
        self.enabled = False
        self.path: Optional[str] = None
        self._f = None
        # JSONL rotation: bytes written to the current sink file and the
        # LIGHTGBM_TPU_TRACE_MAX_MB cap (0 = unbounded).  At the cap the
        # sink rotates to <path>.1 (one generation — a bounded factory
        # run keeps at most 2x the cap on disk) and report loaders read
        # the <path>.1 + <path> pair in order.
        self._bytes = 0
        self._max_bytes = 0
        self._lock = threading.Lock()
        self._stack = []
        self._agg: Dict[str, list] = {}
        self._counters: Dict[str, float] = {}
        self._iter_phases: Optional[Dict[str, float]] = None
        self._iter_idx = None
        self._iter_t0 = 0.0
        self._iter_compiles0 = 0
        self._atexit_registered = False
        # rank/world/run_id stamped onto every record in multi-rank runs
        # so `report merge` can correlate per-rank JSONLs (empty in
        # single-process runs: records stay byte-compatible with PR 1)
        self._ident: Dict[str, Any] = {}
        # tracer-side work counter: every record actually processed
        # (emitted/mirrored) increments it.  The disabled-overhead guard
        # test pins "near-zero when off" on this staying 0 — a counter
        # of work done, not a wall-clock estimate.
        self.work_ops = 0
        # the newest stages kept, oldest first (``stage``): survive
        # ``refresh_from_env()`` and ``close()``, which a traced window calls.
        # A stage's ``depth`` and ``parent`` there count STAGES only, of its
        # own thread: ``_stack`` holds ordinary spans too, while the sink is
        # on, and a kept entry reads the same with the sink on or off
        self.stages = collections.deque(maxlen=STAGES_MAX)
        self._stage_tls = threading.local()

    def _stage_stack(self) -> list:
        """Names of the stages open in this thread, outermost first."""
        try:
            return self._stage_tls.stack
        except AttributeError:
            stack = self._stage_tls.stack = []
            return stack

    # -- lifecycle -----------------------------------------------------
    def refresh_from_env(self) -> None:
        """(Re-)read LIGHTGBM_TPU_TRACE; called at the training entry
        points so tests and the CLI can toggle tracing without importing
        this module early."""
        self._ident_from_env()
        self._max_bytes = _max_bytes_from_env()
        path = os.environ.get("LIGHTGBM_TPU_TRACE", "")
        if path and path != self.path:
            self.configure(path)

    def _ident_from_env(self) -> None:
        """Pre-bootstrap identity from the launcher env (the distributed
        runtime refines it via ``set_identity`` once initialized)."""
        rank = os.environ.get("LIGHTGBM_TPU_PROCESS_ID", "").strip()
        world = os.environ.get("LIGHTGBM_TPU_NUM_PROCESSES", "").strip()
        if rank and world:
            self.set_identity(rank=int(rank), world_size=int(world))

    def set_identity(self, rank: Optional[int] = None,
                     world_size: Optional[int] = None,
                     run_id: Optional[str] = None) -> None:
        """Stamp rank/world_size/run_id onto every subsequent record.
        ``run_id`` defaults to LIGHTGBM_TPU_RUN_ID, else the coordinator
        address — both identical across ranks of one run, which is what
        ``report merge`` verifies before correlating files."""
        if rank is not None:
            self._ident["rank"] = int(rank)
        if world_size is not None:
            self._ident["world"] = int(world_size)
        if run_id is None:
            run_id = (os.environ.get("LIGHTGBM_TPU_RUN_ID", "").strip()
                      or os.environ.get("LIGHTGBM_TPU_COORDINATOR", "").strip())
        if run_id:
            self._ident["run_id"] = str(run_id)

    def configure(self, path: str) -> None:
        """Open (truncate) the JSONL sink at ``path`` and enable tracing."""
        self.close()
        self.path = path
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        self._f = open(path, "w", buffering=1)  # line buffered
        self._bytes = 0
        self._max_bytes = _max_bytes_from_env()
        self.enabled = True
        from . import compilewatch, flight

        compilewatch.install()
        # crash flight recorder: bounded ring of recent records, flushed
        # to <trace>.crash.jsonl by typed net failures / SIGUSR1
        # (obs/flight.py).  Activated ONLY here — tracing off means no
        # ring is ever allocated (the disabled-overhead guard).
        flight.recorder.activate(path)
        self._emit({
            "ev": "meta",
            "version": 1,
            "pid": os.getpid(),
            "argv": sys.argv,
        })
        if not self._atexit_registered:
            atexit.register(self.close)
            self._atexit_registered = True

    def close(self) -> None:
        if self._f is not None:
            try:
                self._f.flush()
                self._f.close()
            except Exception:  # pragma: no cover - interpreter teardown
                pass
            try:
                from . import flight

                flight.recorder.deactivate()
            except Exception:  # pragma: no cover - interpreter teardown
                pass
        self._f = None
        self.enabled = False

    # -- emission ------------------------------------------------------
    def _emit(self, rec: Dict[str, Any]) -> None:
        if self._ident:
            for k, v in self._ident.items():
                rec.setdefault(k, v)
        rec.setdefault("ts", round(time.time(), 6))
        line = json.dumps(rec, default=str)
        self.work_ops += 1
        _flight_recorder().record(rec)
        with self._lock:
            if self._f is not None:
                self._f.write(line + "\n")
                self._bytes += len(line) + 1
                if self._max_bytes and self._bytes >= self._max_bytes:
                    self._rotate_locked()

    def _rotate_locked(self) -> None:
        """Size-capped sink rotation (caller holds ``_lock``): the
        current file becomes ``<path>.1`` (clobbering any previous
        generation) and a fresh sink opens at ``path`` with a new meta
        record so the rotated pair is self-describing."""
        try:
            self._f.flush()
            self._f.close()
            os.replace(self.path, self.path + ".1")
        except OSError:  # pragma: no cover - exotic fs; keep tracing
            pass
        self._f = open(self.path, "w", buffering=1)
        self._bytes = 0
        meta = {"ev": "meta", "version": 1, "pid": os.getpid(),
                "rotated": True, "ts": round(time.time(), 6)}
        meta.update(self._ident)
        line = json.dumps(meta)
        self._f.write(line + "\n")
        self._bytes += len(line) + 1

    def span(self, name: str, **attrs):
        """Timed nested span context manager (no-op singleton when
        disabled — near-zero overhead on hot paths)."""
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, name, attrs)

    def stage(self, name: str, **attrs):
        """A span that is kept: an ordinary span in every respect while the
        sink is on, and on or off one entry of ``self.stages`` when it
        closes: ``name``, ``t0`` (raw ``time.perf_counter()`` at its start),
        ``ts`` (wall clock at its end, as every record has), ``dur_s``,
        ``depth`` and ``parent`` (among the stages open in this thread: an
        ordinary span around it does not count, so the entry reads the same
        with the sink on) and the attributes (``.attrs`` of what this
        returns may be added to until it closes).  Off, that is all it does:
        no JSON, no ``work_ops``, no annotation, no flight-recorder entry.
        Once a ``Dataset``, a ``Booster`` or a compiled program, never once
        an iteration or a chunk."""
        return _Span(self, name, attrs, keep=True)

    def record_stage(self, name: str, t0: float, dur_s: float, **attrs) -> None:
        """A stage that is known to be one only when it is over (a call
        that turned out to build a program, obs/compilewatch.py): ``t0`` and
        ``dur_s`` are the caller's ``perf_counter`` readings.  No annotation."""
        self._close_span(name, t0, dur_s, attrs, True)

    def _close_span(self, name: str, t0: float, dur: float, attrs: Dict[str, Any],
                    keep: bool) -> None:
        if keep:
            outer = self._stage_stack()
            self.stages.append({
                "name": name, "t0": t0, "ts": round(time.time(), 6), "dur_s": dur,
                "depth": len(outer), "parent": outer[-1] if outer else None, **attrs})
        if not self.enabled:
            return
        stack = self._stack
        rec = {
            "ev": "span",
            "name": name,
            "dur_s": round(dur, 9),
            "depth": len(stack),
            "parent": stack[-1] if stack else None,
        }
        if attrs:
            rec.update(attrs)
        self._emit(rec)
        agg = self._agg.setdefault(name, [0.0, 0])
        agg[0] += dur
        agg[1] += 1
        if self._iter_phases is not None:
            self._iter_phases[name] = self._iter_phases.get(name, 0.0) + dur

    def counter(self, name: str, value: float = 1.0, **attrs) -> None:
        if not self.enabled:
            return
        self._counters[name] = self._counters.get(name, 0.0) + value
        rec = {"ev": "counter", "name": name, "value": value}
        rec.update(attrs)
        self._emit(rec)
        from . import metrics

        metrics.registry.trace_counter(name, value)

    def gauge(self, name: str, value: float, **attrs) -> None:
        if not self.enabled:
            return
        rec = {"ev": "gauge", "name": name, "value": value}
        rec.update(attrs)
        self._emit(rec)
        from . import metrics

        metrics.registry.trace_gauge(name, value)

    def event(self, name: str, **attrs) -> None:
        if not self.enabled:
            return
        rec = {"ev": "event", "name": name}
        rec.update(attrs)
        self._emit(rec)

    def write_program_maps(self, modules=None) -> int:
        """Write the phase map of every watched program that has compiled
        (``compilewatch.phase_maps``) to the sink as ``{"ev": "program",
        "name", "module", "matrix", "ops": {instruction: phase}}`` records,
        and return how many.  For an operator who asks: it compiles each
        program again, so nothing on the training path calls it, and
        neither does ``close()``."""
        if not self.enabled:
            return 0
        from . import compilewatch

        maps = compilewatch.phase_maps(modules)
        for m in maps:
            self._emit({"ev": "program", **m})
        return len(maps)

    # -- per-iteration records -----------------------------------------
    @contextlib.contextmanager
    def iteration(self, it: int, **fields):
        """Open a per-iteration record; spans entered inside accumulate
        into its ``phases`` map.  Yields a mutable dict callers can add
        fields to (leaves, bagged_rows, ...).  On close the record gains
        wall time, compile-count delta and memory gauges."""
        if not self.enabled:
            yield None
            return
        from . import compilewatch, memory

        prev_phases = self._iter_phases
        self._iter_phases = {}
        self._iter_idx = it
        c0 = compilewatch.total_compiles()
        t0 = time.perf_counter()
        rec: Dict[str, Any] = dict(fields)
        try:
            yield rec
        finally:
            wall = time.perf_counter() - t0
            out = {
                "ev": "iter",
                "iter": int(it),
                "wall_s": round(wall, 6),
                "phases": {k: round(v, 6) for k, v in self._iter_phases.items()},
                "compiles": compilewatch.total_compiles() - c0,
            }
            out.update(memory.memory_gauges())
            out.update(rec)
            self._emit(out)
            self._iter_phases = prev_phases
            self._iter_idx = None

    def emit_iter(self, it: int, wall_s: float, phases: Dict[str, float],
                  **fields) -> None:
        """Directly write an iteration record (the fused chunk path emits
        amortized per-iteration records after the chunk completes)."""
        if not self.enabled:
            return
        from . import memory

        rec = {
            "ev": "iter",
            "iter": int(it),
            "wall_s": round(wall_s, 6),
            "phases": {k: round(v, 6) for k, v in phases.items()},
        }
        rec.update(memory.memory_gauges())
        rec.update(fields)
        self._emit(rec)

    # -- aggregates ----------------------------------------------------
    def snapshot(self) -> Dict[str, Any]:
        """Host-side aggregate view (phase totals/counts, counters)."""
        return {
            "spans": {
                name: {"total_s": round(t, 6), "count": c,
                       "mean_ms": round(1e3 * t / max(c, 1), 3)}
                for name, (t, c) in sorted(self._agg.items())
            },
            "counters": dict(self._counters),
        }

    def reset_aggregates(self) -> None:
        self._agg.clear()
        self._counters.clear()


tracer = Tracer()


def fence(x):
    """``jax.block_until_ready`` gate used at phase boundaries: a no-op
    unless tracing is enabled, so the async dispatch pipeline is never
    serialized in production runs.  Returns ``x``."""
    if tracer.enabled and x is not None:
        import jax

        jax.block_until_ready(x)
    return x
