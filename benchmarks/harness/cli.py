"""Arguments, the device gate, the call into the cell's driver, the per-layer
readers and the result line."""

import argparse
import importlib.util
import json
import os
import sys

from . import xplane_reduce
from .measure import Run, Spans
from .spec import CACHE_DIR, Spec, SpecError, load_module

NO_TPU = 2  # exit code: no accelerator, or fewer chips than the cell asks for


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(prog="benchmarks/run.py", description=__doc__)
    ap.add_argument("--workload", required=True, help="a name under `workloads` in BENCHMARK.json")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True, help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0,
                    help="1: profile a short window and report the per-layer metrics")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU dry run at the mix's tiny `rehearse` sizes, kernels "
                         "interpreted; reports counts and no metric")
    return ap.parse_args(argv)


def probe_device(chips: int, rehearse: bool) -> dict:
    """The device as JAX reports it, or exit: a time taken off the chip is not
    this benchmark's metric, so there is no fall-back."""
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["LIGHTGBM_TPU_PGROW"] = "force"  # the fused trainer, interpreted
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = f"{flags} --xla_force_host_platform_device_count={chips}".strip()
    try:
        import jax

        devs = jax.devices()
    except RuntimeError as e:  # JAX found no backend at all
        print(f"benchmarks/run.py: JAX found no device: {e}", file=sys.stderr)
        raise SystemExit(NO_TPU)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    if device["platform"] != "tpu" and not rehearse:
        print(f"benchmarks/run.py needs a TPU: JAX found platform {device['platform']!r} "
              f"({device['kind']}); --rehearse makes a CPU dry run that reports no metric",
              file=sys.stderr)
        raise SystemExit(NO_TPU)
    if device["count"] < chips:
        print(f"benchmarks/run.py: the cell asks for {chips} chip(s), JAX found "
              f"{device['count']}", file=sys.stderr)
        raise SystemExit(NO_TPU)
    return device


def layer_metrics(spec: Spec, cell: dict, reported: set, record: dict) -> dict:
    """Every per-layer metric of this cell whose reader finds something."""
    out = {}
    for m in spec.metrics("per_layer", cell["name"]):
        if m["moves"] not in reported:
            continue
        reader = load_module("layer_metrics", m["name"], spec.bench_dir)
        if record["driver"] not in reader.DRIVERS:
            continue
        value = reader.read(record)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def build_result(spec: Spec, cell: dict, trace: bool, rehearse: bool, device: dict, outcome):
    """(the result line's object, the names of the metrics found).  The object
    has the contract's keys and no other; a rehearsal's has no metric."""
    end_to_end = spec.metrics("end_to_end", cell["name"])
    if trace:
        metrics = layer_metrics(spec, cell, {m["name"] for m in end_to_end}, outcome.record)
    else:
        metrics = {m["name"]: {"value": float(outcome.values[m["name"]]), "unit": m["unit"]}
                   for m in end_to_end}
    result = {
        "correct": all(ok for _, ok in outcome.checks),
        "attempted": outcome.attempted,
        "failed": outcome.failed,
        "metrics": {} if rehearse else metrics,
        "device": {**device, "memory_peak_bytes": outcome.memory_peak_bytes},
    }
    if trace and not rehearse:
        reduced = outcome.record["device"]
        result["device"].update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        result["breakdown"] = xplane_reduce.breakdown(reduced)
    return result, sorted(metrics)


def main(argv, t_start: float) -> int:
    args = parse(argv)
    if importlib.util.find_spec("lightgbm_tpu") is None:
        print("benchmarks/run.py: no lightgbm_tpu beside benchmarks/: nothing to measure",
              file=sys.stderr)
        return 1
    try:
        spec = Spec()
        cell = spec.cell(args.workload)
        config, mix = spec.config(cell["config"]), spec.mix(cell["traffic"])
        driver = load_module("drivers", mix["driver"], spec.bench_dir)
    except SpecError as e:
        print(f"benchmarks/run.py: {e}", file=sys.stderr)
        return 1
    if args.rehearse:
        mix = {**mix, **mix["rehearse"]}
    device = probe_device(cell["chips"], args.rehearse)
    log(f"{cell['name']} seed {args.seed}: {device['count']} x {device['kind']} "
        f"({device['platform']}), window {args.seconds} s, trace {args.trace}")

    run = Run(cell=cell, config=config, mix=mix, seed=args.seed, seconds=args.seconds,
              trace=bool(args.trace), rehearse=args.rehearse, device=device,
              spans=Spans(t_start), cache_dir=CACHE_DIR)
    outcome = driver.run(run)

    for what, ok in outcome.checks:
        log(f"{'ok' if ok else 'FAILED'}: {what}")
    log("notes: " + json.dumps(outcome.notes))
    result, found = build_result(spec, cell, bool(args.trace), args.rehearse, device, outcome)
    if args.rehearse:
        log(f"rehearsal on {device['platform']}: no metric is reported off the chip "
            f"(found: {found})")
    print(json.dumps(result), flush=True)  # the last line of stdout, and nothing after it
    return 0
