#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that lightgbm_tpu still starts on the chip.

Drives the main path once, through the entry points a user calls, on a TPU:

  train   ``lgb.train`` on the fused partitioned trainer at the one shape with
          a chip record (binary, synthetic Higgs-shaped 1,000,000 x 28,
          max_bin=63, num_leaves=255), a few iterations, random data from a
          seed; checks the trainer class, compiled (not interpreted) kernels,
          the default grower mode, no retrace, tree sizes and held-out AUC;
          then fused-vs-mask-grower parity at a reduced row count, a fence
          check, and — when the host has four chips — the data-parallel leg.
  serve   ``python -m lightgbm_tpu serve`` over the model the train leg packed:
          /readyz, /predict across bucket sizes against ``Booster.predict``
          (bit-identical), no compile after warm-up, SIGTERM drain, rc 0.

One process per chip: this parent never imports JAX; the train leg and the
server run as sequential children.  Any failed check is a non-zero exit.  What
is printed is smoke output, NOT a benchmark: the numbers go on a labelled
``[chip_smoke]`` line as one JSON summary ending ``"claim": null``.  The last
stdout line is exactly ``{"ok": true, "device": {"platform": ..., "kind": ...,
"count": ...}}`` with the device as JAX reports it; it is printed only on
success.
"""

import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request
from unittest import mock

ROOT = os.path.dirname(os.path.abspath(__file__))

TRAIN_ROWS = 1_000_000
HELDOUT_ROWS = 200_000
TRAIN_ITERS = 16
PARITY_ROWS = 65_536
PARITY_ITERS = 3
# held-out AUC floor for TRAIN_ITERS iterations: this script reads 0.73270 on
# the v5e, every run alike (my chip runs, PR 31: rows from benchmarks/data.py;
# 0.72978 on the rows it drew before); data and seeds are fixed
AUC_FLOOR = 0.72
PARAMS = {
    "objective": "binary",
    "max_bin": 63,
    "num_leaves": 255,
    "learning_rate": 0.1,
    "min_data_in_leaf": 1,
    "min_sum_hessian_in_leaf": 100,
    "verbose": -1,
}
SERVE_BATCHES = (1, 100, 3000)  # cross the power-of-two bucket ladder
STAGE_TIMEOUT_S = 1000


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        log(f"FAILED: {what}")
        raise SystemExit(1)
    log(f"ok: {what}")


# ----------------------------------------------------------------------
# train leg (child process; holds the chip)
# ----------------------------------------------------------------------
def _tree_splits(tree):
    ns = int(tree.num_leaves) - 1
    return (tree.split_feature[:ns].tolist(), tree.threshold_in_bin[:ns].tolist(),
            tree.left_child[:ns].tolist(), tree.right_child[:ns].tolist())


def _jsonl(rows) -> str:
    return "\n".join(json.dumps([float(v) for v in r]) for r in rows) + "\n"


def stage_train(workdir: str) -> None:
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    log(f"jax {jax.__version__}: platform={device['platform']} "
        f"device_kind={device['kind']} devices={device['count']}")
    check(device["platform"] == "tpu",
          f"JAX found a TPU (platform {device['platform']!r}, {device['kind']})")

    import jax.numpy as jnp
    import numpy as np

    import lightgbm_tpu as lgb
    sys.path.insert(1, os.path.join(ROOT, "benchmarks"))  # as benchmarks/run.py has it
    from data import auc as _auc, make_higgs_shaped
    from lightgbm_tpu.boosting.ptrainer import (
        PartitionedTrainer,
        ShardedPartitionedTrainer,
    )
    from lightgbm_tpu.obs import compilewatch
    from lightgbm_tpu.serve.artifact import PredictorArtifact

    check("LIGHTGBM_TPU_LEVELGROW" not in os.environ
          and "LIGHTGBM_TPU_PGROW" not in os.environ,
          "no grower override in the environment (defaults under test)")

    out = {"device": device, "jax": jax.__version__}

    # ---- one-chip training leg at full width ---------------------------
    X, y = make_higgs_shaped(TRAIN_ROWS, seed=7)
    Xt, yt = make_higgs_shaped(HELDOUT_ROWS, seed=11)  # same task, new rows
    t0 = time.perf_counter()
    bst = lgb.train(dict(PARAMS), lgb.Dataset(X, label=y, params=dict(PARAMS)),
                    num_boost_round=TRAIN_ITERS)
    out["train_wall_s"] = round(time.perf_counter() - t0, 2)
    cw = compilewatch.snapshot()
    out["train_compile_s"] = cw["backend_compile_secs"]
    out["train_compile_cache"] = {"hits": cw["cache_hits"],
                                  "misses": cw["cache_misses"]}
    pt = bst.boosting.ptrainer
    check(type(pt) is PartitionedTrainer,
          f"lgb.train ran on PartitionedTrainer (got {type(pt).__name__})")
    check(pt.interpret is False, "kernels compiled through Mosaic (interpret=False)")
    check(pt.params.levelwise is True, "default grower mode (level-batched)")
    leaves = [int(t.num_leaves) for t in bst.boosting.models]
    check(len(leaves) == TRAIN_ITERS, f"{TRAIN_ITERS} trees trained (got {len(leaves)})")
    check(min(leaves) > 1, f"every tree split (min leaves {min(leaves)})")
    check(max(leaves[TRAIN_ITERS // 2:]) == PARAMS["num_leaves"],
          f"late trees reach {PARAMS['num_leaves']} leaves (got {leaves})")
    pred_t = bst.predict(Xt)
    check(pred_t.shape == (HELDOUT_ROWS,) and bool(np.all(np.isfinite(pred_t))),
          "held-out predictions finite, expected shape")
    auc = float(_auc(yt, pred_t))
    out["auc_heldout"] = round(auc, 5)
    check(auc > AUC_FLOOR, f"held-out AUC {auc:.5f} > {AUC_FLOOR} "
          f"after {TRAIN_ITERS} iterations")

    # ---- fence check: one more warm chunk, dispatched by hand ----------
    # does jax.block_until_ready wait for the device, or only a host read?
    (prog,) = pt._progs.values()
    t0 = time.perf_counter()
    res = prog(pt.p, jnp.float32(PARAMS["learning_rate"]), pt._base_key,
               jnp.int32(TRAIN_ITERS), jnp.int32(TRAIN_ITERS))
    t_dispatch = time.perf_counter() - t0
    jax.block_until_ready(res)
    t_block = time.perf_counter() - t0
    float(res[2][0])  # scalar host read of the chunk's scores
    t_read = time.perf_counter() - t0
    pt.p = res[0]
    out["fence"] = {
        "dispatch_s": round(t_dispatch, 4),
        "block_until_ready_s": round(t_block, 4),
        "then_scalar_read_s": round(t_read - t_block, 4),
        "block_until_ready_waits": bool(t_read - t_block < 0.1 * t_block),
    }
    out["warm_chunk_s"] = round(t_read, 3)
    out["warm_chunk_iters"] = TRAIN_ITERS
    log(f"fence check: dispatch {t_dispatch:.4f}s, block_until_ready at "
        f"{t_block:.4f}s, scalar host read +{t_read - t_block:.4f}s")

    # ---- numbers, not just no-crash: fused vs the mask grower ----------
    Xp, yp = X[:PARITY_ROWS], y[:PARITY_ROWS]

    def train_parity():
        return lgb.train(dict(PARAMS), lgb.Dataset(Xp, label=yp, params=dict(PARAMS)),
                         num_boost_round=PARITY_ITERS)

    boosters = {"fused": train_parity()}
    with mock.patch.dict(os.environ, LIGHTGBM_TPU_PGROW="0"):
        boosters["mask"] = train_parity()
    check(type(boosters["fused"].boosting.ptrainer) is PartitionedTrainer
          and boosters["mask"].boosting.ptrainer is None,
          "parity pair: fused trainer vs mask grower (ops/grow.py)")
    sf, sm = (_tree_splits(boosters[m].boosting.models[0]) for m in ("fused", "mask"))
    check(len(sf[0]) > 100 and sf == sm,
          f"first tree split-for-split equal ({len(sf[0])} splits)")
    pf, pm = (boosters[m].predict(Xp) for m in ("fused", "mask"))
    np.testing.assert_allclose(pf, pm, rtol=3e-3, atol=3e-4)
    out["parity_max_abs_diff"] = float(np.max(np.abs(pf - pm)))
    log(f"ok: fused vs mask predictions within rtol 3e-3 / atol 3e-4 over "
        f"{PARITY_ITERS} trees (max abs diff {out['parity_max_abs_diff']:.2e})")

    # ---- four chips: the data-parallel fused trainer --------------------
    if device["count"] >= 4:
        ps = dict(PARAMS, tree_learner="data")
        t0 = time.perf_counter()
        bs = lgb.train(ps, lgb.Dataset(X, label=y, params=dict(ps)),
                       num_boost_round=TRAIN_ITERS)
        sh_wall = time.perf_counter() - t0
        spt = bs.boosting.ptrainer
        check(type(spt) is ShardedPartitionedTrainer and spt.d == device["count"],
              f"tree_learner=data ran on ShardedPartitionedTrainer over "
              f"{device['count']} devices")
        check(spt.interpret is False, "sharded kernels compiled (interpret=False)")
        shard_devs = {s.device for s in spt.p.addressable_shards}
        check(len(shard_devs) == device["count"],
              f"packed matrix shards sit on {len(shard_devs)} distinct devices")
        s1 = [_tree_splits(t) for t in bst.boosting.models]
        s4 = [_tree_splits(t) for t in bs.boosting.models]
        same = sum(a == b for a, b in zip(s1, s4))
        check(s1[0] == s4[0], "sharded first tree equals the one-chip fused tree")
        p4 = bs.predict(Xt)
        np.testing.assert_allclose(p4, pred_t, rtol=3e-3, atol=3e-4)
        out["sharded"] = {"devices": device["count"], "wall_s": round(sh_wall, 2),
                          "identical_trees": same, "trees": TRAIN_ITERS,
                          "max_abs_pred_diff": float(np.max(np.abs(p4 - pred_t)))}
        log(f"ok: sharded leg on {device['count']} devices: {same}/{TRAIN_ITERS} "
            f"trees identical to one chip, predictions within band")
    else:
        out["sharded"] = None
        log(f"sharded leg: not run ({device['count']} devices)")

    cw = compilewatch.snapshot()
    retraces = sum(w["retraces"] for w in cw["watched"].values())
    check(retraces == 0, f"zero jax_retrace flags ({retraces})")
    ms = devs[0].memory_stats() or {}
    out["peak_bytes_in_use"] = ms.get("peak_bytes_in_use")
    out["total_compile_s"] = cw["backend_compile_secs"]
    out["total_compile_cache"] = {"hits": cw["cache_hits"],
                                  "misses": cw["cache_misses"]}

    # ---- hand the model to the serve leg --------------------------------
    PredictorArtifact.from_booster(bst).save(os.path.join(workdir, "model.npz"))
    expected = {}
    for n in SERVE_BATCHES:
        rows = Xt[:n]
        with open(os.path.join(workdir, f"req_{n}.jsonl"), "w") as f:
            f.write(_jsonl(rows))
        expected[str(n)] = [float(v) for v in bst.predict(rows)]
    with open(os.path.join(workdir, "train.json"), "w") as f:
        json.dump({"summary": out, "expected": expected}, f)
    log("train leg summary: " + json.dumps(out))


# ----------------------------------------------------------------------
# serve leg (parent drives the server child over HTTP; no JAX here)
# ----------------------------------------------------------------------
def _http(url: str, body: bytes = None, timeout: float = 120.0):
    req = urllib.request.Request(url, data=body)
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def stage_serve(workdir: str, expected: dict) -> dict:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    base = f"http://127.0.0.1:{port}"
    logf = open(os.path.join(workdir, "serve.log"), "w+")
    t0 = time.perf_counter()
    proc = subprocess.Popen(
        [sys.executable, "-m", "lightgbm_tpu", "serve",
         f"model={os.path.join(workdir, 'model.npz')}", f"port={port}"],
        cwd=ROOT, stdout=logf, stderr=subprocess.STDOUT)
    try:
        code = None
        while code != 200:
            if proc.poll() is not None or time.perf_counter() - t0 > STAGE_TIMEOUT_S:
                check(False, "server reached /readyz 200 (still running, in time)")
            time.sleep(0.5)
            try:
                code, _ = _http(base + "/readyz", timeout=5)
            except (urllib.error.URLError, OSError):
                pass
        ready_s = time.perf_counter() - t0
        log(f"ok: /readyz 200 after {ready_s:.1f}s (bucket-ladder warm-up included)")
        code, body = _http(base + "/stats")
        check(code == 200, "/stats answers")
        warm = json.loads(body)["compiles"]
        for n in SERVE_BATCHES:
            with open(os.path.join(workdir, f"req_{n}.jsonl"), "rb") as f:
                code, body = _http(base + "/predict", f.read())
            check(code == 200, f"/predict batch {n} -> 200")
            got = [json.loads(ln) for ln in body.decode().splitlines() if ln]
            check(got == expected[str(n)],
                  f"/predict batch {n} bit-identical to Booster.predict")
        code, body = _http(base + "/stats")
        after = json.loads(body)["compiles"]
        check(after["backend_compiles"] == warm["backend_compiles"]
              and after["predict_compiles"] == warm["predict_compiles"]
              and after["predict_retraces"] == 0,
              f"no compile after warm-up ({warm['backend_compiles']} before and after)")
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=120)
        logf.seek(0)
        text = logf.read()
        check(rc == 0, f"server exit code 0 after SIGTERM (got {rc})")
        check("drained and stopped" in text, 'server logged "drained and stopped"')
        return {"ready_s": round(ready_s, 2),
                "warmup_compiles": warm["backend_compiles"],
                "warmup_compile_s": warm["backend_compile_secs"],
                "compile_cache": {"hits": warm["cache_hits"],
                                  "misses": warm["cache_misses"]}}
    except BaseException:
        logf.seek(0)
        sys.stderr.write(logf.read()[-4000:])
        raise
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
        logf.close()


def result_line(device: dict) -> str:
    """The contract's last stdout line: exactly ``ok`` and ``device``, the
    device exactly ``platform``/``kind`` (text) and ``count`` (whole number)."""
    return json.dumps({"ok": True, "device": {
        "platform": str(device["platform"]), "kind": str(device["kind"]),
        "count": int(device["count"])}})


def main() -> int:
    if len(sys.argv) == 3 and sys.argv[1] == "--stage-train":
        stage_train(sys.argv[2])
        return 0
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
        rc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--stage-train", workdir],
            cwd=ROOT, timeout=STAGE_TIMEOUT_S).returncode
        if rc != 0:
            log(f"FAILED: train leg exited {rc}")
            return 1
        with open(os.path.join(workdir, "train.json")) as f:
            doc = json.load(f)
        summary = doc["summary"]
        summary["serve"] = stage_serve(workdir, doc["expected"])
    summary["wall_s"] = round(time.perf_counter() - t0, 1)
    device = summary.pop("device")
    log("smoke output, not a benchmark: "
        + json.dumps({"smoke": summary, "claim": None}))
    print(result_line(device), flush=True)  # nothing after it on stdout
    return 0


if __name__ == "__main__":
    sys.exit(main())
