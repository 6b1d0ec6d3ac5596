"""The phase vocabulary of the fused chunk programs, and the join from a
compiled program's text to it.

The chunk programs (boosting/ptrainer.py) and the grower (ops/pgrow.py)
wrap each phase in ``jax.named_scope(<word>)``.  A scope is compile-time
metadata: it costs nothing at run time, and it does NOT reach a profiler
trace (an ``XLA Ops`` event carries a name and a duration, no ``op_name``
stat).  What the trace does carry is every event's whole HLO instruction,
whose name is unique in its module; the compiled module's text carries
``metadata={op_name=".../level_phase/while/body/..."}`` on the
instructions JAX emitted.  So the join is instruction name -> scope,
through the program's own compiled text: :func:`parse_hlo_phases`.

No JAX import here: the parser is text -> dict, and the vocabulary is
shared by the programs, ``JitWatch.phase_map`` and the benchmark's
readers so that they cannot drift.
"""

from __future__ import annotations

import re
from typing import Dict, Optional

# No program opens this scope since PR 30 (a tree starts in the row order the
# previous one left).  The word stays because the benchmark's reader
# (benchmarks/layer_metrics/canon_reorder_ms_per_iter.py) looks it up by name
# and then reads 0.0; a `benchmark` PR retires both.
CANON_REORDER = "canon_reorder"      # until PR 30: rows back to original order at a tree's start
SAMPLE = "sample"                    # bagging / GOSS / feature-fraction draws
UPDATE_ROOT_HIST = "update_root_hist"  # channel refresh + root histogram + root split
LEVEL_PHASE = "level_phase"          # level-batched expansion (level_stream)
SPLIT_SCAN = "split_scan"            # inside level_phase: the split search over a level's histograms
REPLAY = "replay"                    # best-first selection over the candidate tables
REPLAY_TAIL = "replay_tail"          # inside replay: split_stream, once a replayed split
LEAF_DELTA = "leaf_delta"            # segment values -> per-row score delta
SCORE_ADD = "score_add"              # a class's delta onto its score row (K > 1)
CHUNK_EPILOGUE = "chunk_epilogue"    # settle the last delta, scores to original order
# Opened by ops/pgrow._expand_bundle_hist, and so only by a program that
# streams EFB bundles, inside whichever phase searches a histogram: the
# root's (update_root_hist), a level's (split_scan) or a tail split's
# (replay).  One word cannot be enclosed by three, so `phase_of` keys such an
# instruction "<that phase>/bundle_expand" (`nested`), and ENCLOSING hands each
# key to the phase whose readers should still count it.
BUNDLE_EXPAND = "bundle_expand"      # a bundle histogram's (G, BH) planes -> (F, B) per feature

PHASES = (CANON_REORDER, SAMPLE, UPDATE_ROOT_HIST, LEVEL_PHASE, SPLIT_SCAN, REPLAY,
          REPLAY_TAIL, LEAF_DELTA, SCORE_ADD, CHUNK_EPILOGUE, BUNDLE_EXPAND)


def nested(outer: str) -> str:
    return f"{outer}/{BUNDLE_EXPAND}"


# a phase that only ever sits inside another: readers of the outer one add it.
# A reader's sum is one level deep, so a level's bundle expansion goes to
# `level_phase` (whose total stays whole) and `split_scan` reads the search alone.
ENCLOSING = {REPLAY_TAIL: REPLAY, SPLIT_SCAN: LEVEL_PHASE,
             nested(SPLIT_SCAN): LEVEL_PHASE, nested(REPLAY): REPLAY,
             nested(UPDATE_ROOT_HIST): UPDATE_ROOT_HIST}

_COMPUTATION = re.compile(r"^(ENTRY )?%(\S+) \(.*\) -> .*\{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%(\S+) = (\(.*?\)|\S+) ([\w-]+)\(")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLED = re.compile(r"\b(?:condition|body|to_apply|true_computation|false_computation)=%([^\s,)}]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_DIMS = re.compile(r"\[([\d,]*)\]")
_VMAPPED = re.compile(r"^(?:vmap\()+(\w+)\)+$")
# instructions that hand launches to other computations of the module
_CONTROL = ("while", "conditional", "call")


def phase_of(op_name: str) -> Optional[str]:
    """The innermost vocabulary word on an ``op_name`` path, or None; where
    that word is ``bundle_expand``, the word around it with it
    (``split_scan/bundle_expand``).  A
    Pallas kernel's own name (the component before ``pallas_call``) is a
    name, not a scope: ``chunk_epilogue/jit(score_add)/score_add/pallas_call``
    is the epilogue's."""
    parts = op_name.split("/")
    if parts[-1] == "pallas_call":
        parts = parts[:-2]
    # a scope opened under jax.vmap reads "vmap(<word>)", once a level of vmap
    words = [w for w in (_VMAPPED.sub(r"\1", part) for part in parts) if w in PHASES]
    if not words:
        return None
    if words[-1] == BUNDLE_EXPAND:
        outer = [w for w in words if w != BUNDLE_EXPAND]
        return nested(outer[-1]) if outer else BUNDLE_EXPAND
    return words[-1]


def _elements(shape: str) -> int:
    m = _DIMS.search(shape)
    if not m or shape.startswith("("):
        return 0
    n = 1
    for d in m.group(1).split(","):
        n *= int(d) if d else 1
    return n


def _unsharded(shape: str) -> str:
    """``s32[1,512,101024]`` -> ``s32[512,101024]``: under ``shard_map`` a
    device's block of the ``(shards, C, N)`` matrix keeps the sharded axis
    as a leading 1, which the program drops (``pg[0]``) before its loops."""
    return re.sub(r"\[(?:1,)+(?=\d)", "[", shape)


def parse_hlo_phases(text: str) -> Dict[str, object]:
    """``{"module", "matrix", "ops", "matrix_copies"}`` of one compiled
    module's text (``compiled.as_text()``).

    ``ops`` maps the name (no ``%``) of every instruction that can show up
    as an event of its own (those of the entry computation and of the
    bodies, conditions and branches it reaches through ``while``,
    ``conditional`` and ``call``; no parameter, and not the insides of
    fusions and reducers) to its phase:

    - an instruction whose ``op_name`` path has a vocabulary word has the
      innermost one;
    - one without a path (the copies XLA inserts carry no metadata; the
      expansion of a cumsum carries a bare ``reduce_window_sum``) takes the
      phase of the computation it sits in: that of the next instruction of
      the schedule that carries a word (a copy is made for what follows
      it), else of the one before or, where the computation carries none,
      that of the ``while`` or ``conditional`` that calls it;
    - one whose path starts at the program (``jit(prog)/while/body/...``)
      and has no word takes the caller's alone, so that what the program
      really left outside every scope stays ``None``.

    ``matrix`` is the shape of the entry computation's largest parameter,
    layout dropped (the packed matrix, ``s32[16,21001024]``; one device's
    block of it in the data-parallel program, ``s32[1,16,5251024]``), and
    ``matrix_copies`` names the ``copy`` instructions among ``ops`` whose
    result has that shape, with or without the leading 1 of a shard's
    block: the static sites at which the program copies the whole matrix."""
    module = None
    comps = {}    # computation -> [(instruction, shape if a copy, phase or None, has a path)]
    callers = {}  # computation -> (calling computation, calling instruction)
    entry = None
    matrix, matrix_n = None, 0
    cur = None
    for line in text.splitlines():
        if cur is None:
            if module is None and line.startswith("HloModule "):
                module = line.split()[1].rstrip(",")
                continue
            m = _COMPUTATION.match(line)
            if m:
                cur = m.group(2)
                comps[cur] = []
                if m.group(1):
                    entry = cur
            continue
        if line.startswith("}"):
            cur = None
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name, shape, opcode = m.groups()
        if opcode == "parameter":  # never an event of its own
            shape = _LAYOUT.sub("", shape)
            if cur == entry and _elements(shape) > matrix_n:
                matrix, matrix_n = shape, _elements(shape)
            continue
        meta = _OP_NAME.search(line)
        # a path that does not start at the program has lost its scopes
        has_path = meta is not None and meta.group(1).startswith("jit(")
        phase = phase_of(meta.group(1)) if has_path else None
        shape = _LAYOUT.sub("", shape) if opcode == "copy" else None
        comps[cur].append((name, shape, phase, has_path))
        if opcode in _CONTROL:
            called = _CALLED.findall(line)
            for group in _BRANCHES.findall(line):
                called += [c.strip().lstrip("%") for c in group.split(",")]
            for c in called:
                callers[c] = (cur, name)

    ops, copies = {}, []
    matrix_2d = _unsharded(matrix) if matrix else None
    reach = [entry] if entry else []
    while reach:  # callers before the computations they call
        comp = reach.pop(0)
        inherited = ops.get(callers[comp][1]) if comp in callers else None
        rows = comps[comp]
        nxt, fill = inherited, [None] * len(rows)
        for i in range(len(rows) - 1, -1, -1):  # the next instruction that carries a phase
            nxt = rows[i][2] or nxt
            fill[i] = nxt
        prev = inherited
        for (name, copied, phase, has_path), after in zip(rows, fill):
            prev = phase or prev
            ops[name] = phase or (inherited if has_path else (after or prev))
            if copied is not None and _unsharded(copied) == matrix_2d:
                copies.append(name)
        reach += [c for c, (caller, _) in callers.items() if caller == comp and c in comps]
    return {"module": module, "matrix": matrix, "ops": ops, "matrix_copies": copies}
