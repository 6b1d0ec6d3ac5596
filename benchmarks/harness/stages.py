"""Set-up as the program itself measured it: the stages `lightgbm_tpu.obs.tracer`
keeps whether or not its sink is on (`Tracer.stage`: `dataset_construct`,
`booster_init`, `program_build` and what nests in them; the vocabulary is in
docs/OBSERVABILITY.md and benchmarks/STAGES.md).  The benchmark's tracer is off
during set-up, so they are read from the process, not from the record: the
readers run in the process that ran the cell.  A program without stages (a
parent older than them) gives None, and every reader then reports nothing."""

from harness.measure import span_total


def window_start(record):
    """Wall-clock time at which the traced window began: every entry of
    `program_spans` carries its `ts` (wall clock at its end), its `dur_s` and the
    `start_s` from the window's start at which it began."""
    for span in record.get("program_spans") or ():
        if all(k in span for k in ("ts", "dur_s", "start_s")):
            return span["ts"] - span["dur_s"] - span["start_s"]
    return None


def setup_stages(record):
    """The program's kept stages that ENDED before the traced window began, oldest
    first.  That leaves out the parity check's two boosters and the held-out
    predict's programs, which run after the window and before the readers."""
    from lightgbm_tpu.obs import tracer

    kept, start = getattr(tracer, "stages", None), window_start(record)
    if kept is None or start is None:
        return None
    return [s for s in kept if s["ts"] <= start]


def children_share(kept, parent):
    """Share of one kept stage's seconds that its child stages account for: the
    entries one deeper that name it as parent and began and ended inside it.
    `stages_tree.py` prints it beside every parent: under 0.9, a stage is missing."""
    end = parent["t0"] + parent["dur_s"]
    inside = [s["dur_s"] for s in kept
              if s["parent"] == parent["name"] and s["depth"] == parent["depth"] + 1
              and parent["t0"] <= s["t0"] and s["t0"] + s["dur_s"] <= end]
    return sum(inside) / parent["dur_s"] if inside and parent["dur_s"] > 0 else None


def total(record, *names):
    """Summed seconds of the set-up stages with one of those names; None where
    no such stage was kept."""
    kept = setup_stages(record)
    return None if kept is None else span_total(kept, *names)
