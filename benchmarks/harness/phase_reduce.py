"""Device time of a traced window by the phase of the program that spent it.

The fused chunk program names its own phases (`jax.named_scope` words from
`lightgbm_tpu.obs.phases.PHASES`) and its kernels (`pallas_call(name=...)`).
Neither reaches the profiler trace, but the trace names every device event by
its whole HLO instruction, and the program's compiled text says which phase
each instruction belongs to: `lightgbm_tpu.obs.compilewatch.phase_maps()`.
This module lays the two over each other.  Every leaf event of `XLA Ops` goes
to a program (the `XLA Modules` event that covers it) and, through that
program's map, to a phase.  The result, `phases.json` beside the trace:

  window_s, chips, busy_s, idle_s        the window, as xplane_reduce has them
  chunk_program  name, module, matrix, busy_s, launches of the mapped program(s)
  phases         {phase: {busy_s, launches, top: [[operation label, s], ...]}},
                 "(no phase)" for what the program left outside every scope
  other_programs {module: {busy_s, launches}} for programs without a map
  matrix_copies  [{instruction, phase, launches, busy_s}] one per static site at
                 which the program copies the whole packed matrix
  idle_gaps_s    the device's idle time by the innermost `lgbm:` host span
                 ("(no host span)" where none was open)
  phase_maps_s   seconds spent re-lowering the programs for their maps

Seconds and launches are averages over the chips traced.  A program tree older
than the phase vocabulary has no `phase_maps`; `table()` then returns None and
so does every reader.  See benchmarks/PHASES.md.
"""

import bisect
import collections
import json
import os
import re
import time
import traceback

from . import xplane_reduce
from .xplane_reduce import DEVICE_PLANE, OPS_LINE, WINDOW, Event, leaves_and_self, op_label, union

MODULES_LINE = "XLA Modules"
PROGRAM_SPAN = "lgbm:"  # lightgbm_tpu.obs.trace.ANNOTATION_PREFIX
NO_PHASE = "(no phase)"
PHASES_JSON = "phases.json"
_INSTRUCTION = re.compile(r"^%(\S+) = ")
_FINGERPRINT = re.compile(r"\(\d+\)$")
# off the chip a rehearsal's trace has no device plane; the CPU client's thunk
# events carry the same instruction names (their control flow is no launch)
_CPU_SKIP = re.compile(r"^(end: |Thread|Thunk|(while|cond|conditional|call)(\.\d+)*$)")

Reading = collections.namedtuple("Reading", "ops modules host on_device")

_tables = {}  # trace directory -> table or None: every reader of a run reduces once


def _event(e, name: str) -> Event:
    return Event(name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)


def _events(line):
    return [_event(e, e.name) for e in line.events]


def read(path: str) -> Reading:
    """The trace's device operations and module executions by chip, and the
    host's `lgbm:` and `bench:window` annotations; times in seconds.  Without
    a TPU plane (a rehearsal), the CPU client's thunk events stand in as one
    chip's operations, each under its `hlo_module`: they overlap across the
    executor's threads, so their sum is no busy time, only a way to see every
    reader find its input without a chip."""
    from jax.profiler import ProfileData

    ops, modules, host, thunks = {}, {}, [], []
    planes = sorted(ProfileData.from_file(path).planes,
                    key=lambda p: DEVICE_PLANE.match(p.name) is None)  # device planes first
    for plane in planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                ops[int(m.group(1))] = _events(line)
            elif m and line.name == MODULES_LINE:
                modules[int(m.group(1))] = _events(line)
            elif plane.name.startswith("/host:"):
                for e in line.events:
                    if e.name.startswith(PROGRAM_SPAN) or e.name == WINDOW:
                        host.append(_event(e, e.name))
                    elif not ops and not _CPU_SKIP.match(e.name):
                        module = dict(e.stats).get("hlo_module")
                        if module:
                            thunks.append((_event(e, e.name), _event(e, str(module))))
    if ops or not thunks:
        return Reading(ops, modules, host, True)
    # one module event per thunk, so that the same lookup serves both
    return Reading({0: [t for t, _ in thunks]}, {0: [mod for _, mod in thunks]}, host, False)


def _instruction(event_name: str) -> str:
    """`%copy.2308 = s32[...] copy(...)` (a TPU event) or `copy.2308` (a CPU
    thunk) -> `copy.2308`."""
    m = _INSTRUCTION.match(event_name)
    return m.group(1) if m else event_name


def _module_of(mods, starts, t: float):
    """Name of the module execution that covers time t, fingerprint dropped."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and mods[i].start <= t < mods[i].end:
        return _FINGERPRINT.sub("", mods[i].name)
    return None


def _pick_maps(maps, seen):
    """{module: map}: of the maps that share a module name (the cell's chunk
    program and the parity check's are both `jit_prog`), the one that knows
    most of the instructions the trace shows under that name."""
    picked = {}
    for m in maps:
        names = seen.get(m["module"])
        if not names:
            continue
        known = sum(1 for n in names if n in m["ops"])
        if known > picked.get(m["module"], (0, None))[0]:
            picked[m["module"]] = (known, m)
    return {mod: m for mod, (_, m) in picked.items()}


def reduce(reading: Reading, maps, top: int = 8) -> dict:
    """The table of the module docstring from one reading and the programs'
    phase maps.  None if the trace shows no device operation."""
    ops, modules, host, on_device = reading
    if not ops:
        return None
    win = [h for h in host if h.name == WINDOW]
    if win:
        lo, hi = win[0].start, win[0].end
    else:
        lo = min(e.start for evs in ops.values() for e in evs)
        hi = max(e.end for evs in ops.values() for e in evs)
    spans = [h for h in host if h.name != WINDOW]
    n = len(ops)

    # pass 1: leaves, each with its module; which instructions each module shows
    leaves, seen = {}, collections.defaultdict(set)
    for chip, evs in ops.items():
        if on_device:
            evs = xplane_reduce._clip(evs, lo, hi)
            mods = sorted(modules.get(chip, ()), key=lambda e: e.start)
            starts = [e.start for e in mods]
            leaves[chip] = [(e, _module_of(mods, starts, (e.start + e.end) / 2))
                            for e in leaves_and_self(evs)[0]]
        else:  # thunks overlap: each comes with its own module event
            leaves[chip] = [(e, mod.name) for e, mod in zip(evs, modules[chip])
                            if e.end > lo and e.start < hi]
        for e, mod in leaves[chip]:
            seen[mod].add(_instruction(e.name))
    picked = _pick_maps(maps, seen)

    # pass 2: every leaf to its phase
    def cell():
        return {"busy_s": 0.0, "launches": 0.0, "top": collections.Counter()}
    phases = collections.defaultdict(cell)
    others = collections.defaultdict(lambda: {"busy_s": 0.0, "launches": 0.0})
    sites = {}
    for mod, m in picked.items():
        for name in m.get("matrix_copies", ()):
            sites[mod, name] = {"instruction": name, "phase": m["ops"].get(name),
                                "launches": 0.0, "busy_s": 0.0}
    out = {"window_s": hi - lo, "chips": n, "device_plane": on_device,
           "busy_s": 0.0, "idle_s": 0.0}
    program = {"busy_s": 0.0, "launches": 0.0}
    gaps = collections.Counter()
    for chip, found in leaves.items():
        busy = union((e.start, e.end) for e, _ in found)
        out["busy_s"] += sum(e - s for s, e in busy) / n
        for e, mod in found:
            dur = (e.end - e.start) / n
            if mod not in picked:
                others[mod or "(no module)"]["busy_s"] += dur
                others[mod or "(no module)"]["launches"] += 1.0 / n
                continue
            name = _instruction(e.name)
            row = phases[picked[mod]["ops"].get(name) or NO_PHASE]
            row["busy_s"] += dur
            row["launches"] += 1.0 / n
            row["top"][op_label(e.name)[0]] += dur
            program["busy_s"] += dur
            program["launches"] += 1.0 / n
            if (mod, name) in sites:
                sites[mod, name]["busy_s"] += dur
                sites[mod, name]["launches"] += 1.0 / n
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 > g0:
                gaps[xplane_reduce._innermost(spans, (g0 + g1) / 2)] += (g1 - g0) / n
    out["idle_s"] = out["window_s"] - out["busy_s"]
    if picked:
        out["chunk_program"] = {
            "name": sorted(m["name"] for m in picked.values()),
            "module": sorted(picked),
            "matrix": sorted({m["matrix"] for m in picked.values() if m["matrix"]}),
            **program}
    out["phases"] = {
        ph: {"busy_s": row["busy_s"], "launches": row["launches"],
             "top": [[k, v] for k, v in row["top"].most_common(top)]}
        for ph, row in sorted(phases.items(), key=lambda kv: -kv[1]["busy_s"])}
    out["other_programs"] = dict(sorted(others.items(), key=lambda kv: -kv[1]["busy_s"]))
    out["matrix_copies"] = sorted(sites.values(), key=lambda s: -s["busy_s"])
    out["idle_gaps_s"] = dict(gaps)
    return out


def table():
    """The phase table of this process's traced window, reduced once and kept;
    also written to phases.json beside the trace.  None where there is nothing
    to reduce: a program without `phase_maps`, no trace, no device operation.
    The trace directory is the directory of the program tracer's sink."""
    try:
        from lightgbm_tpu.obs import compilewatch, tracer
    except ImportError:
        return None
    if not hasattr(compilewatch, "phase_maps") or not tracer.path:
        return None
    trace_dir = os.path.dirname(tracer.path)
    if trace_dir not in _tables:
        try:
            _tables[trace_dir] = _reduce_dir(trace_dir, compilewatch)
        except Exception:  # the run's own result must not hang on this reduction
            traceback.print_exc()
            _tables[trace_dir] = None
    return _tables[trace_dir]


def _reduce_dir(trace_dir: str, compilewatch):
    try:
        reading = read(xplane_reduce.find_xplane(trace_dir))
    except FileNotFoundError:
        return None
    shown = {_FINGERPRINT.sub("", e.name) for evs in reading.modules.values() for e in evs}
    t0 = time.perf_counter()
    maps = compilewatch.phase_maps(modules=shown)
    maps_s = time.perf_counter() - t0
    out = reduce(reading, maps)
    if out is None:
        return None
    out["phase_maps_s"] = maps_s
    with open(os.path.join(trace_dir, PHASES_JSON), "w") as f:
        json.dump(out, f, indent=1)
    return out


def phase_total(tab: dict, phase: str, key: str = "busy_s") -> float:
    """`busy_s` or `launches` of a phase, with the phases that only ever sit
    inside it (`replay_tail` in `replay`); 0.0 if the window never ran it."""
    from lightgbm_tpu.obs.phases import ENCLOSING

    names = [phase] + [inner for inner, outer in ENCLOSING.items() if outer == phase]
    return sum(tab["phases"].get(p, {}).get(key, 0.0) for p in names)


def phase_ms(record: dict, constant: str, per: str):
    """What five readers report: milliseconds of the phase that
    `lightgbm_tpu.obs.phases.<constant>` names, per `record[per]` (`iters`, or
    `laps` for what is paid once a chunk).  None without a table."""
    tab = table()
    if tab is None:
        return None
    from lightgbm_tpu.obs import phases

    return 1e3 * phase_total(tab, getattr(phases, constant)) / record[per]
