"""Observability layer: structured run tracing, compile/retrace
accounting, the chunk programs' phase vocabulary, memory gauges and trace
reports.

Import surface (kept tiny — hot paths touch only ``tracer``/``fence``):

  from lightgbm_tpu.obs import tracer, fence
  tracer.refresh_from_env()           # LIGHTGBM_TPU_TRACE=trace.jsonl
  with tracer.span("histogram"): ...  # + an "lgbm:histogram" profiler annotation
  with tracer.iteration(i) as rec: rec["leaves"] = 31
  from lightgbm_tpu.obs import PHASES, compilewatch
  compilewatch.phase_maps()           # device time by phase: see obs/phases.py
  tracer.write_program_maps()         # the same, as "program" records in the sink

Submodules: ``trace`` (spans/counters/gauges/iteration records, JSONL
sink with LIGHTGBM_TPU_TRACE_MAX_MB rotation; an enabled span is also a
``jax.profiler.TraceAnnotation("lgbm:<name>")``), ``phases`` (the flat
vocabulary of ``jax.named_scope`` words the fused chunk programs wrap
their phases in — ``PHASES``: sample, update_root_hist, level_phase,
split_scan, replay, replay_tail, leaf_delta, score_add, chunk_epilogue,
and canon_reorder, which no program opens since PR 30 —
and ``parse_hlo_phases``, the pure text -> {instruction: phase} join
through a compiled module's ``op_name`` metadata), ``compilewatch``
(jax.monitoring compile counter + JitWatch retrace detector, which
remembers the abstract signature of its last compile so that
``JitWatch.phase_map()`` / ``phase_maps()`` can rebuild the program's
phase map on demand, + the first-compile HLO cost capture), ``costmodel``
(per-program flops/bytes inventory, peak-spec roofline, per-phase
efficiency attribution), ``memory`` (host/device gauges), ``report``
(aggregation + the ``python -m lightgbm_tpu report`` CLI, incl. the
cross-rank ``merge``, audit ``diff`` and ``costs`` subcommands),
``metrics`` (Prometheus text-format registry behind ``GET /metrics``),
``audit`` (LIGHTGBM_TPU_AUDIT split-decision trail),
``flight`` (crash flight recorder dumping to ``<trace>.crash.jsonl``).
"""

from .trace import Tracer, fence, tracer  # noqa: F401
from .compilewatch import JitWatch  # noqa: F401
from .phases import PHASES  # noqa: F401

__all__ = ["Tracer", "tracer", "fence", "JitWatch", "PHASES"]
