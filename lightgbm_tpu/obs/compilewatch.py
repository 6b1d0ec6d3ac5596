"""JAX compile / retrace accountant.

Unexpected retraces are the classic silent TPU perf killer: a jitted
program whose closure bakes in a trace-time value (an env var, a python
float) silently recompiles — or worse, silently does NOT pick up a
changed value — and nothing in the training log shows it.  This module
provides two layers:

1. A process-global compile counter fed by ``jax.monitoring`` duration
   events (``/jax/core/compile/backend_compile_duration`` fires once per
   XLA backend compilation, persistent-cache hits included).  Each
   compile also lands in the trace as a ``jax_compile`` event.

2. ``JitWatch`` — a wrapper for jitted entry points that tracks the
   jit cache size per *array signature* (shapes + dtypes of array
   arguments).  When the cache grows on a signature that has already
   been traced, the call is flagged as an **unexpected retrace**
   (``jax_retrace`` trace event + Log.warning): the cache key changed
   through something invisible in the arguments — exactly the
   env-var-read-at-trace-time class of bug.

Both layers are cheap enough to stay on unconditionally: the monitoring
listener fires only on compiles, and a ``JitWatch`` call adds two cache
-size reads and two clock reads per invocation (the fused trainer invokes
its chunk program once per 64 iterations).  A call on which the jit cache
grew is kept as a ``program_build`` stage (obs/trace.py): what a program's
first call cost, sink on or off; less its ``backend_s`` that is what the
persistent cache does NOT skip (tracing to a jaxpr, lowering it to MLIR).
"""

from __future__ import annotations

import time
from typing import Any, Dict

from ..utils.log import Log

_counts = {"backend_compiles": 0, "backend_compile_secs": 0.0,
           "cache_hits": 0, "cache_misses": 0}
# persistent compilation cache outcomes (jax/_src/compiler.py): a hit is
# a compile request answered from the cache directory, a miss is one
# that compiled and was written there
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
                 "/jax/compilation_cache/cache_misses": "cache_misses"}
_installed = False
_watches = []


def install() -> None:
    """Register the jax.monitoring listener (idempotent)."""
    global _installed
    if _installed:
        return
    import jax.monitoring

    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)
    _installed = True


def _on_event(name: str, **kwargs) -> None:
    key = _CACHE_EVENTS.get(name)
    if key is not None:
        _counts[key] += 1


def _on_duration(name: str, secs: float, **kwargs) -> None:
    if name != "/jax/core/compile/backend_compile_duration":
        return
    _counts["backend_compiles"] += 1
    _counts["backend_compile_secs"] += secs
    from .trace import tracer

    if tracer.enabled:
        tracer.event("jax_compile", secs=round(secs, 4))


def total_compiles() -> int:
    return _counts["backend_compiles"]


def snapshot() -> Dict[str, Any]:
    """Aggregate compile accounting for bench output / reports."""
    return {
        "backend_compiles": _counts["backend_compiles"],
        "backend_compile_secs": round(_counts["backend_compile_secs"], 3),
        "cache_hits": _counts["cache_hits"],
        "cache_misses": _counts["cache_misses"],
        "watched": {
            w.name: {
                "calls": w.calls,
                "compiles": w.compiles,
                "retraces": w.retraces,
                "signatures": len(w._sigs),
            }
            for w in _watches
        },
    }


def phase_maps(modules=None) -> list:
    """The phase map (``JitWatch.phase_map``) of every watched program that
    has compiled; ``modules`` keeps only the programs whose XLA module name
    (``jit_prog``) is in it.  On demand only: each map lowers and compiles
    its program again (a load, with the persistent compile cache), so this
    is for after a run, never for the training path."""
    maps = []
    for w in list(_watches):
        if modules is not None and w.module not in modules:
            continue
        try:
            maps.append(w.phase_map())
        except Exception as e:  # one program that will not lower hides no other
            Log.warning("phase map failed for %s: %s", w.name, e)
    return [m for m in maps if m is not None]


def _abstract(args, kwargs):
    """(args, kwargs) with every array leaf replaced by its
    ``ShapeDtypeStruct`` (a sharding over a mesh kept, since it is part of
    the program) and every other leaf (static values, Python scalars) as
    it was: what ``lower`` needs to rebuild the same program, in a few
    hundred bytes and holding no buffer."""
    import jax
    from jax.sharding import SingleDeviceSharding

    def leaf(x):
        if not (hasattr(x, "shape") and hasattr(x, "dtype")):
            return x
        sharding = getattr(x, "sharding", None)
        if isinstance(sharding, SingleDeviceSharding):
            sharding = None
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding,
                                    weak_type=getattr(x, "weak_type", False))

    return jax.tree_util.tree_map(leaf, (args, kwargs))


def _sig_of(args, kwargs):
    """Array signature: (shape, dtype, sharding) per array leaf;
    non-array leaves are deliberately EXCLUDED so a cache key that
    shifts without any visible argument change is caught as a retrace.
    Sharding IS part of jax's cache key (a device_put onto a mesh
    legitimately recompiles at the same shape), so it belongs in the
    signature — without it the serving layer's row-sharded predict reads
    as a false retrace of the single-device program."""
    import jax

    leaves = jax.tree_util.tree_leaves((args, kwargs))
    return tuple(
        (tuple(l.shape), str(l.dtype), str(getattr(l, "sharding", "")))
        for l in leaves
        if hasattr(l, "shape") and hasattr(l, "dtype")
    )


class JitWatch:
    """Wrap a jitted callable; count compilations per array signature and
    flag cache growth on an already-seen signature as a retrace.

    ``phase`` tags the program with the measured phase-span name it
    accounts under (``histogram``, ``chunk_program``, ``serve_batch``,
    ...) so the cost model (obs/costmodel.py) can join its HLO roofline
    against the wall-clock the trace measured for that phase."""

    def __init__(self, fn, name: str, phase: str = None):
        import threading

        self._fn = fn
        self.name = name
        self.phase = phase
        self.calls = 0
        self.compiles = 0
        self.retraces = 0
        self._sigs = set()
        self._last_cache_size = 0
        # abstract (args, kwargs) of the last compile, for phase_map()
        self._compiled_spec = None
        # serialize calls so a concurrent caller's compile can't land
        # inside another caller's before/after window and read as that
        # caller's (false) retrace — the serving batchers share one watch
        self._lock = threading.Lock()
        install()
        _watches.append(self)

    def __call__(self, *args, **kwargs):
        from jax.core import trace_ctx

        # called while an OUTER jit is tracing: this program is inlined
        # into the caller's jaxpr — no backend compile happens here, and
        # the cache bookkeeping below would misread the outer trace's
        # state.  Call straight through (the module-level kernel watches
        # in ops/pgrow.py and ops/histogram.py hit this constantly).
        if not trace_ctx.is_top_level():
            return self._fn(*args, **kwargs)
        with self._lock:
            return self._call_locked(args, kwargs)

    def _call_locked(self, args, kwargs):
        self.calls += 1
        before = self._fn._cache_size()
        # a shrunken cache means jax.clear_caches() (or a backend
        # teardown) emptied the jit cache out from under us: every seen
        # signature will legitimately compile again, so the seen set is
        # from a dead cache lifetime — forget it instead of flagging the
        # whole re-warm as retraces
        if before < self._last_cache_size:
            self._sigs.clear()
        counts0 = dict(_counts)
        t0 = time.perf_counter()
        out = self._fn(*args, **kwargs)
        dur = time.perf_counter() - t0
        after = self._last_cache_size = self._fn._cache_size()
        if after > before:
            self.compiles += 1
            sig = _sig_of(args, kwargs)
            self._compiled_spec = _abstract(args, kwargs)
            from .trace import tracer

            # what this program's first call cost, up to the call's return
            # (trace, lower, compile or cache load, dispatch; not the
            # device's run); ``backend_s`` is the back-end compile's share,
            # a cache load where ``cache_hit``
            grew = {k: _counts[k] - counts0[k] for k in _counts}
            tracer.record_stage(
                "program_build", t0, dur, program=self.name,
                backend_s=grew["backend_compile_secs"],
                cache_hit=grew["cache_hits"] > 0 and grew["cache_misses"] == 0)
            if sig in self._sigs:
                self.retraces += 1
                Log.warning(
                    "unexpected retrace of %s (jit cache grew %d -> %d on an "
                    "already-traced argument signature) — a trace-time "
                    "constant changed outside the cache key (env var read "
                    "inside the traced function?)",
                    self.name, before, after,
                )
                tracer.event("jax_retrace", fn=self.name,
                             cache_size=after)
            else:
                self._sigs.add(sig)
                tracer.event("jax_trace", fn=self.name, cache_size=after)
                self._record_cost(args, kwargs, grew["backend_compile_secs"], sig)
        return out

    @property
    def module(self) -> str:
        """The XLA module name of the wrapped program, as a profiler
        trace's ``XLA Modules`` line and the compiled text have it."""
        return "jit_" + getattr(self._fn, "__name__", "")

    def phase_map(self) -> Dict[str, Any]:
        """``{"name", "module", "matrix", "ops": {instruction: phase}}`` of
        the program as last compiled (obs/phases.py): lowered and compiled
        again from the remembered abstract signature, and its text parsed.
        None before the first compile."""
        if self._compiled_spec is None:
            return None
        from .phases import parse_hlo_phases

        args, kwargs = self._compiled_spec
        text = self._fn.lower(*args, **kwargs).compile().as_text()
        return {"name": self.name, **parse_hlo_phases(text)}

    def _record_cost(self, args, kwargs, compile_secs, sig=None):
        """First compile per signature: scrape HLO cost/memory analysis
        into the program inventory + a ``jax_cost`` trace record
        (obs/costmodel.py).  Only when tracing is enabled (the capture
        re-lowers the program once — not free), and never allowed to
        break the training step."""
        from .trace import tracer

        if not tracer.enabled:
            return
        try:
            from . import costmodel

            costmodel.capture(self, args, kwargs, compile_secs, sig=sig)
        except Exception as e:
            Log.warning("cost capture failed for %s: %s", self.name, e)
