#!/usr/bin/env python3
"""One run of a cell as `run.py` makes it, and then, on standard error, the
program's kept stages as a tree: when each began (seconds from process start),
its seconds and attributes, and beside every stage that has children the share
of it they account for.  PERF.md's set-up table is made from this, and ISSUE
38's rule that the children close 90% of `booster_init` and `dataset_construct`
is read here: the readers under `layer_metrics/` report the sums, not the tree.

    python3 benchmarks/stages_tree.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The result line is still the last line of standard output."""

import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(1, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness.cli import main  # noqa: E402
from harness.stages import children_share  # noqa: E402

OWN = ("name", "t0", "ts", "dur_s", "depth", "parent", "children_share")


def tree(kept):
    """The kept stages in the order they began, each with `children_share`."""
    kept = sorted(kept, key=lambda s: s["t0"])
    return [{**s, "children_share": children_share(kept, s)} for s in kept]


if __name__ == "__main__":
    rc = main(sys.argv[1:], T_START)
    from lightgbm_tpu.obs import tracer

    for s in tree(getattr(tracer, "stages", ())):
        share = "" if s["children_share"] is None else f"  children {s['children_share']:.1%}"
        attrs = {k: v for k, v in s.items() if k not in OWN}
        print(f"[stages] {s['t0'] - T_START:9.3f} {'  ' * s['depth']}{s['name']} "
              f"{s['dur_s']:.3f}{share}  {attrs or ''}", file=sys.stderr)
    sys.exit(rc)
