"""Compiled-program cost accounting — HLO roofline model.

Five rounds of perf work (PRs 6, 8, 12-15) answered "faster than last
time?"; this module answers "how far from the machine?".  Every
``JitWatch``-wrapped program records, at first compile per argument
signature, XLA's HLO cost analysis (flops, bytes accessed,
transcendentals) and — when a re-compile is cheap enough to afford —
the compiled memory analysis (peak temp / argument / output bytes).
Each capture lands as a ``jax_cost`` trace record AND in a
process-global program inventory, so the offline report
(``python -m lightgbm_tpu report costs <trace>``) can join program
costs against measured phase spans.

The join produces, per phase, an **efficiency %**: the roofline
lower-bound time (``max(flops/peak_flops, bytes/peak_bw)`` per call,
times the measured call count) divided by the measured wall.  The
"next kernel target" is the phase with the most reclaimable wall —
``measured - roofline`` — which is exactly "lowest efficiency weighted
by share of wall".

Peak specs are nominal public per-chip numbers (bf16 MXU flops + HBM
bandwidth); override or extend with ``LIGHTGBM_TPU_PEAK_SPECS`` as a
JSON object, e.g.::

  LIGHTGBM_TPU_PEAK_SPECS='{"cpu": {"flops_per_s": 1e11,
                                    "hbm_bytes_per_s": 3e10}}'

Spec keys are matched case-insensitively as substrings of the JAX
``device_kind`` (longest key wins), so "tpu v5 lite" matches the
device kind ``TPU v5 lite``; a kind no key matches raises.  The ``cpu``
row is a rough host-class number for CPU test runs, where the point is
the *relative* phase ranking and nothing it yields is a device metric.
"""

from __future__ import annotations

import json
import math
import os
import threading
from typing import Any, Dict, List, Optional

from ..utils.log import LightGBMError, Log

# Nominal per-chip peaks: bf16 MXU flops + HBM bandwidth (public specs;
# v4 275 Tflops / 1228 GB/s, v5e ("v5 lite") 197 Tflops / 819 GB/s,
# v5p 459 Tflops / 2765 GB/s).  The cpu row is a nominal host-class
# vector unit + DRAM figure, present so CPU test runs still produce a
# ranking.
DEFAULT_PEAK_SPECS: Dict[str, Dict[str, float]] = {
    "tpu v4": {"flops_per_s": 275e12, "hbm_bytes_per_s": 1228e9},
    "tpu v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
    "tpu v5e": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
    "tpu v5p": {"flops_per_s": 459e12, "hbm_bytes_per_s": 2765e9},
    "cpu": {"flops_per_s": 1e11, "hbm_bytes_per_s": 3e10},
}

# at most this many per-signature cost records are kept per program —
# the serving bucket ladder can legitimately compile dozens of shapes
_MAX_SIGS_PER_PROGRAM = 8

_lock = threading.Lock()
# program name -> {"phase": str|None, "backend": str, "records": [dict]}
_inventory: Dict[str, Dict[str, Any]] = {}
# (program, signature) pairs already captured this process — JitWatch
# instances are rebuilt per trainer, so without this a suite that trains
# many boosters re-pays the lower()/AOT-compile capture for the same
# program+shapes on every run
_captured: set = set()


def reset() -> None:
    """Clear the process-global program inventory (tests)."""
    with _lock:
        _inventory.clear()
        _captured.clear()


def enabled() -> bool:
    """Cost capture kill switch: LIGHTGBM_TPU_COSTMODEL=0 disables the
    lower/cost-analysis pass at first compile (it re-traces the program
    once, which a latency-critical caller may not want to pay)."""
    return os.environ.get("LIGHTGBM_TPU_COSTMODEL", "1") != "0"


def deep_budget_s() -> float:
    """Compile-time budget (seconds) under which the capture also runs
    ``lowered.compile()`` for the post-optimization memory analysis.
    The AOT compile is NOT shared with the dispatch cache, so a program
    that took 30 s to compile would take ~30 s again — the budget keeps
    the deep pass to programs whose observed backend compile was cheap
    (default 2 s)."""
    try:
        return float(os.environ.get("LIGHTGBM_TPU_COSTMODEL_DEEP_BUDGET",
                                    "2.0"))
    except ValueError:
        return 2.0


# ----------------------------------------------------------------------
# peak specs + roofline arithmetic
# ----------------------------------------------------------------------
def peak_specs() -> Dict[str, Dict[str, float]]:
    """Default spec table merged with the LIGHTGBM_TPU_PEAK_SPECS JSON
    override (override wins per key; malformed JSON warns and is
    ignored)."""
    specs = {k: dict(v) for k, v in DEFAULT_PEAK_SPECS.items()}
    raw = os.environ.get("LIGHTGBM_TPU_PEAK_SPECS", "").strip()
    if raw:
        try:
            user = json.loads(raw)
            if not isinstance(user, dict):
                raise ValueError("not a JSON object")
            for k, v in user.items():
                row = specs.setdefault(str(k).lower(), {})
                row.update({kk: float(vv) for kk, vv in v.items()})
                row["source"] = "env"
        except (ValueError, TypeError, AttributeError) as e:
            Log.warning("ignoring malformed LIGHTGBM_TPU_PEAK_SPECS: %s", e)
    return specs


def resolve_peak_spec(device_kind: Optional[str] = None) -> Dict[str, Any]:
    """Pick the spec row for ``device_kind`` (default: the first JAX
    device's kind).  Keys match case-insensitively as substrings of the
    kind, longest key first.  A device that is not in the table is an
    error, not the ``cpu`` row: a roofline against the wrong peaks is
    worse than none (add the device through LIGHTGBM_TPU_PEAK_SPECS)."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    kind = str(device_kind).lower()
    specs = peak_specs()
    match = next((key for key in sorted(specs, key=len, reverse=True)
                  if key in kind), None)
    if match is None:
        raise LightGBMError(
            f"no peak-spec row for device kind {device_kind!r} (known: "
            f"{sorted(specs)}); add one via LIGHTGBM_TPU_PEAK_SPECS")
    row = specs[match]
    return {
        "key": match,
        "device_kind": str(device_kind),
        "flops_per_s": float(row["flops_per_s"]),
        "hbm_bytes_per_s": float(row["hbm_bytes_per_s"]),
        "source": row.get("source", "default"),
    }


def roofline(flops: float, bytes_accessed: float, transcendentals: float,
             spec: Dict[str, Any]) -> Dict[str, Any]:
    """Roofline estimate for one program call: arithmetic intensity
    (flop/byte), compute- vs memory-bound verdict against the spec's
    ridge point, and the lower-bound seconds per call.  Transcendentals
    are charged as one flop each (XLA counts them separately)."""
    pf = float(spec["flops_per_s"])
    pb = float(spec["hbm_bytes_per_s"])
    work = float(flops) + float(transcendentals)
    compute_s = work / pf if pf > 0 else 0.0
    memory_s = float(bytes_accessed) / pb if pb > 0 else 0.0
    ai = (work / float(bytes_accessed)) if bytes_accessed > 0 else math.inf
    return {
        "ai": round(ai, 4) if math.isfinite(ai) else None,
        "ridge_ai": round(pf / pb, 2) if pb > 0 else None,
        "bound": "compute" if compute_s >= memory_s else "memory",
        "lb_s": max(compute_s, memory_s),
    }


# ----------------------------------------------------------------------
# capture (called from JitWatch at first compile per signature)
# ----------------------------------------------------------------------
def _nbytes(leaves) -> int:
    total = 0
    for l in leaves:
        shape = getattr(l, "shape", None)
        dtype = getattr(l, "dtype", None)
        if shape is None or dtype is None:
            continue
        n = 1
        for d in shape:
            n *= int(d)
        total += n * int(getattr(dtype, "itemsize", 4))
    return total


def _cost_dict(cost) -> Dict[str, float]:
    """Normalize a cost_analysis() result: Lowered returns a flat dict,
    Compiled returns a one-element list of dicts."""
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    if not isinstance(cost, dict):
        return {}
    return cost


def capture(watch, args, kwargs, compile_secs: Optional[float],
            sig=None) -> Optional[dict]:
    """Scrape HLO cost/memory analysis for a freshly-compiled signature
    of ``watch`` (a JitWatch) and record it: ``jax_cost`` trace event +
    process-global inventory row.  Returns the record, or None when the
    capture is disabled, the callable has no AOT surface, or the work
    would be thrown away (program+signature already captured this
    process, or the program's inventory is full) — the skip check runs
    BEFORE the lower() so a suite that trains many boosters does not
    re-pay the re-trace per booster."""
    if not enabled():
        return None
    with _lock:
        if sig is not None and (watch.name, sig) in _captured:
            return None
        entry = _inventory.get(watch.name)
        if entry is not None and len(entry["records"]) >= _MAX_SIGS_PER_PROGRAM:
            return None
    import jax

    fn = watch._fn
    lower = getattr(fn, "lower", None)
    if lower is None:
        return None
    lowered = lower(*args, **kwargs)
    cost = _cost_dict(lowered.cost_analysis())
    rec: Dict[str, Any] = {
        "program": watch.name,
        "phase": watch.phase,
        "backend": str(jax.devices()[0].device_kind),
        "level": "lowered",
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "transcendentals": float(cost.get("transcendentals", 0.0)),
        "arg_bytes": _nbytes(jax.tree_util.tree_leaves((args, kwargs))),
        "out_bytes": _nbytes(jax.tree_util.tree_leaves(lowered.out_info)),
        "compile_secs": round(float(compile_secs or 0.0), 4),
    }
    # deep pass: a real AOT compile (NOT shared with the dispatch cache)
    # for the post-optimization cost + memory analysis — only when the
    # observed backend compile was cheap enough to pay twice
    if compile_secs is not None and compile_secs <= deep_budget_s():
        try:
            compiled = lowered.compile()
            dcost = _cost_dict(compiled.cost_analysis())
            if dcost:
                rec["flops"] = float(dcost.get("flops", rec["flops"]))
                rec["bytes_accessed"] = float(
                    dcost.get("bytes accessed", rec["bytes_accessed"]))
                rec["transcendentals"] = float(
                    dcost.get("transcendentals", rec["transcendentals"]))
            mem = compiled.memory_analysis()
            if mem is not None:
                rec["temp_bytes"] = int(
                    getattr(mem, "temp_size_in_bytes", 0))
                rec["arg_bytes"] = int(
                    getattr(mem, "argument_size_in_bytes", rec["arg_bytes"]))
                rec["out_bytes"] = int(
                    getattr(mem, "output_size_in_bytes", rec["out_bytes"]))
                rec["code_bytes"] = int(
                    getattr(mem, "generated_code_size_in_bytes", 0))
            rec["level"] = "compiled"
        except Exception as e:  # pragma: no cover - backend-specific AOT gaps
            Log.warning("deep cost pass failed for %s: %s", watch.name, e)
    _record(rec)
    if sig is not None:
        with _lock:
            _captured.add((watch.name, sig))
    return rec


def _record(rec: Dict[str, Any]) -> None:
    with _lock:
        entry = _inventory.setdefault(rec["program"], {
            "phase": rec.get("phase"),
            "backend": rec.get("backend"),
            "records": [],
        })
        if len(entry["records"]) < _MAX_SIGS_PER_PROGRAM:
            entry["records"].append(dict(rec))
    from .trace import tracer

    tracer.event("jax_cost", **{k: v for k, v in rec.items()})


def inventory() -> Dict[str, Dict[str, Any]]:
    """Snapshot of the process-global program inventory."""
    with _lock:
        return {k: {"phase": v["phase"], "backend": v["backend"],
                    "records": [dict(r) for r in v["records"]]}
                for k, v in _inventory.items()}


# ----------------------------------------------------------------------
# join: program costs x measured phase spans -> efficiency table
# ----------------------------------------------------------------------
def programs_from_trace(records: List[Dict[str, Any]]) -> Dict[str, Dict[str, Any]]:
    """Rebuild the program inventory from ``jax_cost`` records of a
    JSONL trace stream (the offline mirror of :func:`inventory`)."""
    by: Dict[str, Dict[str, Any]] = {}
    for r in records:
        if r.get("ev") != "event" or r.get("name") != "jax_cost":
            continue
        entry = by.setdefault(str(r.get("program")), {
            "phase": r.get("phase"),
            "backend": r.get("backend"),
            "records": [],
        })
        if len(entry["records"]) < _MAX_SIGS_PER_PROGRAM:
            entry["records"].append(r)
    return by


def phase_stats_from_trace(records: List[Dict[str, Any]]) -> Dict[str, Dict[str, Any]]:
    """{span name: {"total_s", "count"}} over a trace stream."""
    out: Dict[str, Dict[str, Any]] = {}
    for r in records:
        if r.get("ev") != "span":
            continue
        agg = out.setdefault(str(r.get("name", "?")),
                             {"total_s": 0.0, "count": 0})
        agg["total_s"] += float(r.get("dur_s", 0.0))
        agg["count"] += 1
    return out


def program_stats(entry: Dict[str, Any], spec: Dict[str, Any]) -> Dict[str, Any]:
    """Per-program cost summary: means across recorded signatures (the
    bucket-ladder programs compile many shapes; the mean is the honest
    single number when per-signature call counts are unknown) plus the
    roofline verdict on those means."""
    recs = entry.get("records") or []
    n = max(len(recs), 1)
    flops = sum(float(r.get("flops", 0.0)) for r in recs) / n
    nbytes = sum(float(r.get("bytes_accessed", 0.0)) for r in recs) / n
    trans = sum(float(r.get("transcendentals", 0.0)) for r in recs) / n
    rl = roofline(flops, nbytes, trans, spec)
    out = {
        "phase": entry.get("phase"),
        "signatures": len(recs),
        "flops_per_call": flops,
        "bytes_per_call": nbytes,
        "transcendentals_per_call": trans,
        "ai": rl["ai"],
        "bound": rl["bound"],
        "roofline_s_per_call": rl["lb_s"],
        "level": (recs[-1].get("level") if recs else None),
    }
    temps = [int(r["temp_bytes"]) for r in recs if r.get("temp_bytes")]
    if temps:
        out["peak_temp_bytes"] = max(temps)
    return out


def efficiency_table(phase_stats: Dict[str, Dict[str, Any]],
                     programs: Dict[str, Dict[str, Any]],
                     spec: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Join program rooflines against measured phase spans.

    When several programs map to one phase, the one with the largest
    per-call roofline represents the phase — the others are variants of
    the same work, and one span = one call of the representative.  Rows sort by measured wall, descending."""
    by_phase: Dict[str, List[str]] = {}
    for name, entry in programs.items():
        ph = entry.get("phase")
        if ph:
            by_phase.setdefault(str(ph), []).append(name)
    rows: List[Dict[str, Any]] = []
    total_measured = 0.0
    for ph, names in by_phase.items():
        meas = phase_stats.get(ph)
        if not meas or meas.get("count", 0) <= 0:
            continue
        stats = {n: program_stats(programs[n], spec) for n in names}
        rep = max(names, key=lambda n: stats[n]["roofline_s_per_call"])
        st = stats[rep]
        measured = float(meas["total_s"])
        count = int(meas["count"])
        roof = st["roofline_s_per_call"] * count
        eff = 100.0 * roof / measured if measured > 0 else None
        rows.append({
            "phase": ph,
            "program": rep,
            "calls": count,
            "measured_s": round(measured, 6),
            "roofline_s": round(roof, 6),
            "efficiency_pct": round(eff, 2) if eff is not None else None,
            "headroom_s": round(max(measured - roof, 0.0), 6),
            "ai": st["ai"],
            "bound": st["bound"],
        })
        total_measured += measured
    for row in rows:
        row["share_pct"] = round(
            100.0 * row["measured_s"] / total_measured, 1
        ) if total_measured > 0 else None
    rows.sort(key=lambda r: -r["measured_s"])
    return rows


def next_target(rows: List[Dict[str, Any]]) -> Optional[Dict[str, Any]]:
    """The machine-picked optimization target: the phase with the most
    reclaimable wall (measured - roofline) — equivalently, the lowest
    efficiency weighted by share of wall."""
    candidates = [r for r in rows if r.get("headroom_s", 0.0) > 0.0]
    if not candidates:
        return None
    return max(candidates, key=lambda r: r["headroom_s"])


def next_target_line(rows: List[Dict[str, Any]]) -> str:
    t = next_target(rows)
    if t is None:
        return ""
    eff = t.get("efficiency_pct")
    eff_txt = f"{eff:.1f}%" if eff is not None else "n/a"
    return (f"next kernel target: {t['phase']} ({t['program']}) — "
            f"{eff_txt} of roofline at {t['share_pct']:.1f}% of phase "
            f"wall, headroom {t['headroom_s']:.3f} s")


def costs_summary(records: List[Dict[str, Any]],
                  spec: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """Full cost-model summary from a loaded trace stream: resolved
    peak spec, per-program inventory stats, the per-phase efficiency
    table and the next-target pick."""
    programs = programs_from_trace(records)
    if spec is None:
        backend = next((e.get("backend") for e in programs.values()
                        if e.get("backend")), None)
        spec = resolve_peak_spec(backend)
    table = efficiency_table(phase_stats_from_trace(records), programs, spec)
    return {
        "peak_spec": spec,
        "n_programs": len(programs),
        "n_signatures": sum(len(e["records"]) for e in programs.values()),
        "programs": {n: program_stats(e, spec)
                     for n, e in sorted(programs.items())},
        "table": table,
        "next_target": next_target(table),
        "next_target_line": next_target_line(table),
    }


# ----------------------------------------------------------------------
# rendering
# ----------------------------------------------------------------------
def _fmt_si(x: float) -> str:
    for div, suf in ((1e12, "T"), (1e9, "G"), (1e6, "M"), (1e3, "k")):
        if abs(x) >= div:
            return f"{x / div:.2f}{suf}"
    return f"{x:.0f}"


def render_costs(summary: Dict[str, Any], path: str = "") -> str:
    """Text table for ``report costs``."""
    spec = summary["peak_spec"]
    lines = []
    lines.append(
        f"=== lightgbm_tpu cost-model report{': ' + path if path else ''} ===")
    src = " (LIGHTGBM_TPU_PEAK_SPECS)" if spec.get("source") == "env" else ""
    lines.append(
        f"peak spec [{spec['key']}{src}] for {spec['device_kind']}: "
        f"{_fmt_si(spec['flops_per_s'])}flop/s, "
        f"{_fmt_si(spec['hbm_bytes_per_s'])}B/s "
        f"(ridge AI {spec['flops_per_s'] / spec['hbm_bytes_per_s']:.1f} "
        f"flop/B)")
    rows = summary["table"]
    if rows:
        lines.append("")
        lines.append(f"{'phase':<16}{'program':<28}{'calls':>7}"
                     f"{'measured_s':>12}{'roofline_s':>12}{'eff%':>8}"
                     f"{'AI':>8}{'bound':>9}{'share%':>8}")
        for r in rows:
            eff = f"{r['efficiency_pct']:.2f}" \
                if r.get("efficiency_pct") is not None else "-"
            ai = f"{r['ai']:.2f}" if r.get("ai") is not None else "inf"
            lines.append(
                f"{r['phase']:<16}{r['program']:<28}{r['calls']:>7}"
                f"{r['measured_s']:>12.4f}{r['roofline_s']:>12.6f}"
                f"{eff:>8}{ai:>8}{r['bound']:>9}"
                f"{r['share_pct']:>8.1f}")
    else:
        lines.append("")
        lines.append("no joinable phases (trace has no jax_cost records, "
                     "or no spans matching a program's phase tag)")
    progs = summary["programs"]
    if progs:
        lines.append("")
        lines.append(
            f"program inventory ({summary['n_programs']} programs, "
            f"{summary['n_signatures']} signatures):")
        lines.append(f"{'program':<30}{'sigs':>6}{'flops/call':>12}"
                     f"{'bytes/call':>12}{'AI':>8}{'bound':>9}"
                     f"{'roofline_ms':>13}")
        for name, st in progs.items():
            ai = f"{st['ai']:.2f}" if st.get("ai") is not None else "inf"
            lines.append(
                f"{name:<30}{st['signatures']:>6}"
                f"{_fmt_si(st['flops_per_call']):>12}"
                f"{_fmt_si(st['bytes_per_call']):>12}{ai:>8}"
                f"{st['bound']:>9}"
                f"{1e3 * st['roofline_s_per_call']:>13.4f}")
    line = summary.get("next_target_line")
    if line:
        lines.append("")
        lines.append(line)
    return "\n".join(lines) + "\n"
