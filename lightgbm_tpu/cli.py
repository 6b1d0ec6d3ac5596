"""CLI application — counterpart of src/application/application.cpp +
src/main.cpp: ``python -m lightgbm_tpu task=train config=train.conf``
accepts the reference's key=value argv and .conf files unmodified
(LoadParameters, application.cpp:48-104).
"""

from __future__ import annotations

import os
import sys
import time
from typing import Dict, List

import numpy as np

from .basic import Booster, Dataset
from .config import PARAM_ALIASES, Config, canonicalize_params
from .utils.log import Log

# Exit codes (docs/ROBUSTNESS.md).  sysexits-flavored so supervisors can
# tell a retryable infrastructure death from a config/data error:
# EX_TEMPFAIL (75) = a peer died; restarting the job auto-resumes from
# the last checkpoint.  EX_IOERR (74) = a collective or the distributed
# bootstrap timed out with peers apparently alive (lost collective,
# blackholed link) — also retryable, but worth alerting on.
EXIT_PEER_FAILURE = 75
EXIT_NET_TIMEOUT = 74


def parse_argv(argv: List[str]) -> Dict[str, str]:
    """key=value argv parsing (LoadParameters, application.cpp:48-61)."""
    params: Dict[str, str] = {}
    for arg in argv:
        if "=" in arg:
            key, _, value = arg.partition("=")
            key = key.strip().strip('"').strip("'")
            value = value.strip().strip('"').strip("'")
            if key:
                params[key] = value
        else:
            Log.warning("Unknown parameter in command line: %s", arg)
    return params


def parse_config_file(path: str) -> Dict[str, str]:
    """.conf parsing with '#' comments (application.cpp:66-98)."""
    params: Dict[str, str] = {}
    if not os.path.exists(path):
        Log.warning("Config file %s doesn't exist, will ignore", path)
        return params
    with open(path) as f:
        for line in f:
            if "#" in line:
                line = line[: line.index("#")]
            line = line.strip()
            if not line:
                continue
            if "=" in line:
                key, _, value = line.partition("=")
                key = key.strip().strip('"').strip("'")
                value = value.strip().strip('"').strip("'")
                if key:
                    params[key] = value
            else:
                Log.warning("Unknown parameter in config file: %s", line)
    return params


def load_all_params(argv: List[str]) -> Dict[str, str]:
    params = parse_argv(argv)
    # resolve config/config_file alias before reading the file
    cfg_path = params.get("config_file") or params.get("config")
    if cfg_path:
        file_params = parse_config_file(cfg_path)
        for key, value in file_params.items():
            # command line has higher priority (application.cpp:87-89)
            canon = PARAM_ALIASES.get(key, key)
            if key not in params and canon not in params and not any(
                PARAM_ALIASES.get(k, k) == canon for k in params
            ):
                params[key] = value
    params.pop("config", None)
    params.pop("config_file", None)
    return params


def run_train(config: Config, params: Dict[str, str]) -> None:
    """InitTrain + Train (application.cpp:188-250).

    Fault tolerance (docs/CHECKPOINT.md): ``snapshot_freq`` now writes
    REAL training-state checkpoints through ``ckpt/`` (the reference's
    periodic model-text dump is still emitted alongside for reference
    compat), and ``task=train`` auto-resumes an interrupted run from the
    latest valid checkpoint in ``output_model``'s directory — the
    resumed run is bit-identical to one that never died.  SIGTERM
    (preemption) flushes a checkpoint at the next iteration boundary and
    exits cleanly."""
    if not config.data:
        Log.fatal("No training data, application quit")
    train_ds = Dataset(config.data, params=dict(params))
    booster = Booster(params=dict(params), train_set=train_ds)
    for i, vpath in enumerate(config.valid_data):
        name = os.path.basename(vpath)
        booster.add_valid(train_ds.create_valid(vpath), name)
    if config.is_save_binary_file:
        train_ds.save_binary(config.data + ".bin")

    from .ckpt import CheckpointManager, PreemptionExit
    from .obs import flight
    from .parallel.net import NetError

    # live-run forensics: SIGUSR1 flushes the flight-recorder ring to
    # <trace>.crash.jsonl without disturbing training (docs/OBSERVABILITY.md)
    flight.install_signal_handler()

    b = booster.boosting
    num_iters = config.num_iterations
    ckpt_freq = config.checkpoint_freq or config.snapshot_freq
    resume = str(config.checkpoint_resume).lower()
    mgr = None
    start_iter = 0
    if ckpt_freq > 0 or resume == "force":
        ckpt_dir = config.checkpoint_dir or (
            os.path.dirname(os.path.abspath(config.output_model))
        )
        mgr = CheckpointManager(ckpt_dir, freq=max(ckpt_freq, 0),
                                keep_last=config.checkpoint_keep)
        mgr.install_signal_handlers()
        if resume not in ("false", "0", "none", ""):
            state = mgr.try_restore(
                booster, require=(resume == "force"),
                ignore_complete=(resume == "force"),
            )
            if state is not None:
                start_iter = state.iteration
                Log.info("Resuming training from checkpoint at iteration %d",
                         start_iter)

    # LIGHTGBM_TPU_XPROF=<dir>: bounded device-profiler capture across a
    # few steady-state iterations (utils/profiling.XprofCapture) — the
    # ROADMAP recapture sweep needs only the env var, no code
    from .utils.profiling import maybe_xprof_capture

    xprof = maybe_xprof_capture()
    Log.info("Started training...")
    try:
        for it in range(start_iter, num_iters):
            start = time.time()
            if xprof is not None:
                xprof.on_iter_start()
            finished = b.train_one_iter(is_eval=True)
            if xprof is not None:
                xprof.on_iter_end()
            Log.info("%f seconds elapsed, finished iteration %d",
                     time.time() - start, it + 1)
            if config.snapshot_freq > 0 and (it + 1) % config.snapshot_freq == 0:
                # reference-compat model text alongside the real checkpoint
                snap = f"{config.output_model}.snapshot_iter_{it + 1}"
                b.save_model_to_file(snap)
                Log.info("Saved snapshot to %s", snap)
            if mgr is not None:
                mgr.maybe_save(booster)
            if finished:
                Log.info("Early stopping at iteration %d", it + 1)
                break
    except PreemptionExit as px:
        mgr.flush()
        Log.warning(
            "Training preempted: checkpoint flushed at iteration %d; "
            "rerun task=train (or `python -m lightgbm_tpu resume`) to "
            "continue bit-identically", px.step,
        )
        return
    except NetError:
        # peer failure / collective timeout: keep the last completed
        # checkpoint durable and let main() map the typed error to a
        # retryable exit code (docs/ROBUSTNESS.md cooperative abort)
        if mgr is not None:
            mgr.flush()
        raise
    finally:
        if xprof is not None:
            xprof.close()
    if mgr is not None:
        mgr.mark_complete(booster)
        mgr.close()
    b.save_model_to_file(config.output_model)
    Log.info("Finished training, model saved to %s", config.output_model)
    _dump_metrics_if_requested()


def _dump_metrics_if_requested() -> None:
    """End-of-train Prometheus dump: LIGHTGBM_TPU_METRICS=path writes
    the registry (compile accounting + every mirrored trace counter and
    gauge) in the exposition text format — the offline twin of the
    serve front end's live ``GET /metrics``."""
    path = os.environ.get("LIGHTGBM_TPU_METRICS", "").strip()
    if not path:
        return
    from .obs.metrics import registry

    try:
        registry.dump(path)
        Log.info("Metrics dumped to %s", path)
    except OSError as e:
        Log.warning("Could not dump metrics to %s: %s", path, e)


def run_ingest(config: Config, params: Dict[str, str]) -> None:
    """task=ingest (TPU extension): stream a text file through the
    out-of-core pipeline (data/ingest.py) into the binary dataset cache
    ``<data>.bin`` — the raw float matrix is never materialized, so
    arbitrarily large files prep on a bounded-memory host.  Training
    then loads the cache (DatasetLoader::LoadFromBinFile path)."""
    import json

    from .data.ingest import stream_dataset
    from .obs import tracer

    if not config.data:
        Log.fatal("No data for ingest, application quit")
    tracer.refresh_from_env()
    ds = stream_dataset(config.data, config)
    out = config.data + ".bin"
    ds.save_binary(out, source_path=config.data)
    report = dict(getattr(ds, "ingest_report", {}))
    report["output"] = out
    Log.info("Finished ingest: %s", json.dumps(report))


def run_convert_model(config: Config, params: Dict[str, str]) -> None:
    """task=convert_model (application.cpp:268-273): emit the standalone
    C++ if-else predictor (convert_model.py <- GBDT::ModelToIfElse)."""
    from .basic import Booster
    from .convert_model import model_to_cpp

    if not config.input_model:
        Log.fatal("No model file for convert_model, application quit")
    if config.convert_model_language not in ("", "cpp"):
        Log.fatal("Unsupported convert_model_language %s (only cpp)",
                  config.convert_model_language)
    booster = Booster(model_file=config.input_model)
    out = config.convert_model or "gbdt_prediction.cpp"
    with open(out, "w") as f:
        f.write(model_to_cpp(booster.boosting))
    Log.info("Finished converting model to C++ code, saved to %s", out)


def run_predict(config: Config, params: Dict[str, str]) -> None:
    """Predict path (application.cpp:252-260, predictor.hpp)."""
    if not config.data:
        Log.fatal("No data for prediction, application quit")
    if not config.input_model:
        Log.fatal("No model file for prediction, application quit")
    booster = Booster(params=dict(params), model_file=config.input_model)
    preds = booster.predict(
        config.data,
        num_iteration=config.num_iteration_predict,
        raw_score=config.is_predict_raw_score,
        pred_leaf=config.is_predict_leaf_index,
    )
    preds = np.atleast_1d(preds)
    with open(config.output_result, "w") as f:
        if preds.ndim == 1:
            for v in preds:
                f.write(f"{v:g}\n")
        else:
            for row in preds:
                f.write("\t".join(f"{v:g}" for v in row) + "\n")
    Log.info("Finished prediction, results saved to %s", config.output_result)


def main(argv: List[str] = None) -> int:
    """Application::Run (application.h:82, main.cpp:4-21).

    Two non-reference extensions: ``python -m lightgbm_tpu report
    <trace.jsonl>`` renders a TIMETAG-style summary of a structured run
    trace (docs/OBSERVABILITY.md), and ``python -m lightgbm_tpu serve
    model=... [key=value ...]`` runs the microbatching HTTP predict
    server over a packed artifact or model file (docs/SERVING.md)."""
    if argv is None:
        argv = sys.argv[1:]
    if argv and argv[0] == "report":
        from .obs.report import main as report_main

        return report_main(argv[1:])
    if argv and argv[0] == "serve":
        from .serve.server import main as serve_main

        return serve_main(argv[1:])
    if argv and argv[0] == "fleet":
        from .serve.fleet import main as fleet_main

        return fleet_main(argv[1:])
    if argv and argv[0] == "factory":
        from .factory.supervisor import main as factory_main

        return factory_main(argv[1:])
    if argv and argv[0] == "ingest":
        # subcommand sugar for task=ingest (matches report/serve style)
        argv = ["task=ingest"] + argv[1:]
    if argv and argv[0] == "resume":
        # subcommand sugar: task=train that REQUIRES a checkpoint to
        # resume from (docs/CHECKPOINT.md); plain task=train already
        # auto-resumes an interrupted run
        argv = ["task=train", "checkpoint_resume=force"] + argv[1:]
    from .parallel.net import CollectiveTimeoutError, PeerFailureError

    try:
        params = load_all_params(argv)
        config = Config.from_params(params)
        if config.task == "train":
            run_train(config, params)
        elif config.task in ("predict", "prediction", "test"):
            run_predict(config, params)
        elif config.task == "convert_model":
            run_convert_model(config, params)
        elif config.task == "ingest":
            run_ingest(config, params)
        else:
            Log.fatal("Unknown task type %s", config.task)
    except PeerFailureError as ex:
        Log.warning(
            "Peer failure after %.1fs (ranks %s): %s — restart the job to "
            "auto-resume from the last checkpoint",
            ex.elapsed_s, list(ex.ranks), ex,
        )
        return _net_exit(EXIT_PEER_FAILURE)
    except CollectiveTimeoutError as ex:
        Log.warning(
            "Collective/bootstrap timeout after %.1fs: %s — restart the "
            "job to auto-resume from the last checkpoint",
            ex.elapsed_s, ex,
        )
        return _net_exit(EXIT_NET_TIMEOUT)
    except Exception as ex:  # main.cpp catches and exits non-zero
        try:  # fatal path: leave a flight-recorder dump alongside the trace
            from .obs import flight

            flight.dump("fatal_error", error=ex)
        except Exception:
            pass
        Log.warning("Met Exceptions: %s", ex)
        return 1
    return 0


def _net_exit(code: int) -> int:
    """Leave after a transport failure.  In a multi-process runtime the
    survivors must NOT run interpreter atexit hooks: the JAX distributed
    shutdown barrier blocks ~100 s against the dead peer and then kills
    the process with a fatal log — so exit through ``net.hard_exit``.
    Single-process (bootstrap timeouts) returns normally."""
    from .parallel import net

    if net._client() is not None:
        import jax

        if jax.process_count() > 1:
            net.hard_exit(code)  # never returns
    return code


if __name__ == "__main__":
    sys.exit(main())
