"""``CheckpointManager`` — the training-side checkpoint driver.

Used two ways:

  - as an after-iteration **callback** (``engine.train`` threads it into
    the callback list; ``order=40`` puts it after ``early_stopping`` so
    the captured callback state is current through the iteration);
  - **directly** by the CLI's training loop via :meth:`maybe_save`.

Capture is synchronous (device arrays are pulled at a consistent
iteration boundary); serialization + the fsync'd write happen on a
single background worker thread, so steady-state training overlaps the
disk write.  At most one write is in flight: the next save
waits for the previous one, bounding buffered checkpoint memory to one
blob.

Preemption: :meth:`install_signal_handlers` arms SIGTERM (the shape of
a preemptible-VM warning).  The flag is checked at the next iteration
boundary, where the manager writes a final checkpoint *synchronously*
and raises :class:`PreemptionExit`; ``engine.train`` / the CLI catch it,
finalize, and return — the next run auto-resumes bit-identically.

Multihost protocol: every host captures its local state and enters an
allgather barrier carrying its iteration number (``parallel/collect.py``
— KV-store transport on XLA:CPU, device allgather elsewhere).  The
barrier proves all hosts sit on the same iteration; host 0 then writes
one container blob holding every host's state.  On resume each host
reads the same file and restores its own rank's entry.
"""

from __future__ import annotations

import concurrent.futures
import io
import json
import signal
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

from ..obs import tracer
from ..utils.log import Log
from .state import (CheckpointMismatch, TrainState, capture,
                    combine_fingerprint_parts, data_fingerprint_parts,
                    merge_to_canonical, reshard_to_local, restore)
from .store import CheckpointStore


class PreemptionExit(RuntimeError):
    """Raised at an iteration boundary after a preemption signal once
    the final checkpoint is safely on disk."""

    def __init__(self, step: int):
        super().__init__(f"preempted; checkpoint flushed at iteration {step}")
        self.step = step


def _wrap_hosts(blobs: List[bytes]) -> bytes:
    """Per-host TrainState blobs -> one container npz."""
    payload = {f"rank_{r}": np.frombuffer(b, np.uint8) for r, b in enumerate(blobs)}
    payload["__hosts__"] = np.asarray(len(blobs), np.int64)
    buf = io.BytesIO()
    np.savez(buf, **payload)
    return buf.getvalue()


def _unwrap_host(blob: bytes, rank: int) -> bytes:
    """Extract this host's TrainState blob (identity for single-host)."""
    with np.load(io.BytesIO(blob), allow_pickle=False) as z:
        if "__hosts__" not in z.files:
            return blob
        hosts = int(z["__hosts__"])
        if rank >= hosts:
            raise ValueError(
                f"checkpoint holds {hosts} host states but this is rank {rank}"
            )
        return z[f"rank_{rank}"].tobytes()


class CheckpointManager:
    """Periodic TrainState checkpointing with background writes."""

    order = 40  # after early_stopping (30): its state is current
    before_iteration = False

    def __init__(self, directory: str, freq: int = 0, keep_last: int = 3,
                 background: bool = True):
        self.store = CheckpointStore(directory, keep_last=keep_last)
        self.freq = int(freq)
        self.background = bool(background)
        self._executor: Optional[concurrent.futures.ThreadPoolExecutor] = None
        self._pending: Optional[concurrent.futures.Future] = None
        self._preempt = threading.Event()
        self._tracked: List[Any] = []
        self._last_saved = -1
        self.stats: Dict[str, Any] = {
            "saves": 0, "bytes": 0, "save_s": [], "capture_s": [],
        }

    # -- wiring --------------------------------------------------------
    def track_callbacks(self, callbacks) -> None:
        """Register callbacks whose closure state must survive resume
        (those exposing ``ckpt_state``/``ckpt_restore``)."""
        self._tracked = [cb for cb in callbacks
                         if hasattr(cb, "ckpt_state") and cb is not self]

    def install_signal_handlers(self, signals=(signal.SIGTERM,)) -> None:
        """Arm preemption signals: the handler only sets a flag; the
        flush happens at the next iteration boundary on the main
        thread (signal-safe by construction)."""
        def _handler(signum, frame):
            Log.warning(
                "Received signal %d: flushing a checkpoint at the next "
                "iteration boundary, then exiting", signum,
            )
            self._preempt.set()

        for sig in signals:
            signal.signal(sig, _handler)

    def request_preemption(self) -> None:
        """Programmatic preemption (tests / embedding runtimes)."""
        self._preempt.set()

    @property
    def preempted(self) -> bool:
        return self._preempt.is_set()

    # -- callback protocol ---------------------------------------------
    def __call__(self, env) -> None:
        self.maybe_save(env.model)

    # -- core ----------------------------------------------------------
    def maybe_save(self, booster, force: bool = False) -> bool:
        """Checkpoint when the iteration counter sits on a ``freq``
        boundary (or ``force``).  Raises :class:`PreemptionExit` after a
        flush triggered by a preemption signal."""
        step = int(booster.boosting.iter)
        if self._preempt.is_set():
            if step != self._last_saved:
                self.save(booster, sync=True)
            else:
                self.flush()
            raise PreemptionExit(step)
        if not force:
            if self.freq <= 0 or step <= 0 or step % self.freq != 0:
                return False
        if step == self._last_saved:
            return False
        self.save(booster)
        return True

    def save(self, booster, sync: bool = False) -> int:
        """Capture + write one checkpoint; returns the step."""
        t0 = time.perf_counter()
        state = capture(booster, extra_py=self._callback_state())
        self.stats["capture_s"].append(time.perf_counter() - t0)
        step = state.iteration
        with tracer.span("ckpt.serialize", iter=step):
            blob = state.to_bytes()

        import jax

        nproc = jax.process_count()
        if nproc > 1:
            from ..parallel.collect import allgather_bytes
            from ..parallel.net import NetError

            try:
                with tracer.span("ckpt.barrier", iter=step):
                    gathered = allgather_bytes(step.to_bytes(8, "little") + blob)
            except NetError as e:
                # a peer died or the collective timed out mid-barrier:
                # nothing from THIS boundary is durable, but the last
                # completed checkpoint is — flush the writer so it is
                # fully on disk and surface the failure for the
                # cooperative abort path (engine/cli auto-resume)
                self.flush()
                Log.warning(
                    "Checkpoint barrier at iteration %d failed (%s); the "
                    "last completed checkpoint remains the resume point",
                    step, e,
                )
                raise
            steps = [int.from_bytes(g[:8], "little") for g in gathered]
            if len(set(steps)) != 1:
                Log.fatal(
                    "Checkpoint barrier saw divergent iterations across "
                    "hosts: %s", steps,
                )
            self._last_saved = step
            if jax.process_index() != 0:
                return step  # host 0 owns the write
            # canonical global layout (docs/CHECKPOINT.md): merge the
            # rank states into one global-row-order container so the
            # checkpoint resumes at ANY world size, not just this one
            with tracer.span("ckpt.merge_canonical", iter=step,
                             world=nproc):
                blob = merge_to_canonical(
                    [TrainState.from_bytes(g[8:]) for g in gathered]
                ).to_bytes()

        self._last_saved = step
        if self.background and not sync:
            self._submit_write(step, blob, t0)
        else:
            self.flush()
            self._write(step, blob, t0)
        return step

    def _submit_write(self, step: int, blob: bytes, t0: float) -> None:
        if self._executor is None:
            self._executor = concurrent.futures.ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="ckpt-writer"
            )
        self.flush()  # one write in flight: bounds buffered blobs to one
        self._pending = self._executor.submit(self._write, step, blob, t0)

    def _write(self, step: int, blob: bytes, t0: float) -> None:
        try:
            path = self.store.save(step, blob)
        except Exception as e:  # pragma: no cover - disk-full etc.
            Log.warning("Checkpoint write for iteration %d failed: %s", step, e)
            return
        dur = time.perf_counter() - t0
        self.stats["saves"] += 1
        self.stats["bytes"] = len(blob)
        self.stats["save_s"].append(dur)
        tracer.counter("ckpt.bytes", len(blob))
        tracer.event("ckpt.saved", iter=step, bytes=len(blob),
                     secs=round(dur, 4), path=path)
        Log.info("Checkpoint saved at iteration %d (%d bytes)", step, len(blob))

    def flush(self) -> None:
        """Wait for the in-flight background write, if any."""
        if self._pending is not None:
            self._pending.result()
            self._pending = None

    def close(self) -> None:
        self.flush()
        if self._executor is not None:
            self._executor.shutdown(wait=True)
            self._executor = None

    def mark_complete(self, booster) -> None:
        """Training finished normally: flush and leave a completion
        marker so the next fresh run doesn't auto-resume a done run."""
        self.flush()
        self.store.mark_complete(int(booster.boosting.iter))

    # -- resume --------------------------------------------------------
    def try_restore(self, booster, require: bool = False,
                    ignore_complete: bool = False) -> Optional[TrainState]:
        """Restore the latest valid checkpoint into ``booster``.

        Returns the restored state, or ``None`` when there is nothing to
        resume (no valid checkpoint, or the previous run completed and
        ``ignore_complete`` is not set).  Fingerprint mismatches raise
        ``CheckpointMismatch`` — resume never silently retrains."""
        latest = self.store.latest_valid()
        if latest is None:
            if require:
                Log.fatal("No valid checkpoint found in %s", self.store.dir)
            return None
        if not ignore_complete and self.store.complete_step() is not None:
            Log.info(
                "Checkpoints in %s belong to a completed run; starting fresh",
                self.store.dir,
            )
            return None
        step, blob = latest

        import jax

        rank, nproc = jax.process_index(), jax.process_count()
        blob = _unwrap_host(blob, rank)  # legacy per-rank containers only
        state = TrainState.from_bytes(blob)
        if "world_size" in state.meta:
            state = self._reshard_to_current(booster, state, rank, nproc)
        restore(booster, state)
        self._restore_callbacks(state)
        self._last_saved = step
        return state

    def _reshard_to_current(self, booster, state: TrainState, rank: int,
                            nproc: int) -> TrainState:
        """Adapt a canonical global-layout checkpoint to the current
        topology.  All ranks enter in lockstep (they all read the same
        container): a tiny allgather of per-rank row counts + CRC
        primitives establishes the current partition and proves the
        concatenated shards are byte-for-byte the saved global dataset
        before any state is sliced."""
        b = booster.boosting
        local_rows = int(b.num_data)
        valid_rows = [int(np.asarray(vs).shape[1]) for vs in b.valid_scores]
        parts = data_fingerprint_parts(b.train_set)
        entry = {"rows": local_rows, "valid": valid_rows, "parts": parts}
        if nproc > 1:
            from ..parallel.collect import allgather_bytes

            gathered = [
                json.loads(g)
                for g in allgather_bytes(
                    json.dumps(entry).encode(), purpose="ckpt_reshard")
            ]
        else:
            gathered = [entry]
        shard_rows = [int(g["rows"]) for g in gathered]
        valid_shard = [[int(g["valid"][i]) for g in gathered]
                       for i in range(len(valid_rows))]
        global_fp = combine_fingerprint_parts([g["parts"] for g in gathered])
        if global_fp != state.meta["data_fingerprint"]:
            raise CheckpointMismatch(
                "checkpoint was written against a different global dataset "
                f"(checkpoint {state.meta['data_fingerprint']}, run "
                f"{global_fp}); refusing to resume"
            )
        local_fp = combine_fingerprint_parts([parts])
        saved_w = int(state.meta.get("world_size", 1))
        if saved_w != nproc:
            Log.info(
                "Resharding checkpoint from world size %d to %d "
                "(canonical global layout)", saved_w, nproc,
            )
        return reshard_to_local(
            state, rank, shard_rows, valid_shard, local_fp,
            bag_seed=int(getattr(b.config, "bagging_seed", 0)),
        )

    # -- tracked-callback state ----------------------------------------
    def _callback_state(self) -> Dict[str, Any]:
        out = {}
        for i, cb in enumerate(self._tracked):
            name = getattr(cb, "ckpt_name", type(cb).__name__)
            try:
                out[f"cb/{i}/{name}"] = cb.ckpt_state()
            except Exception as e:  # pragma: no cover - defensive
                Log.warning("callback %s state capture failed: %s", name, e)
        return {"callbacks": json.loads(json.dumps(out, default=str))} if out else {}

    def _restore_callbacks(self, state: TrainState) -> None:
        saved = state.py.get("callbacks") or {}
        for i, cb in enumerate(self._tracked):
            name = getattr(cb, "ckpt_name", type(cb).__name__)
            st = saved.get(f"cb/{i}/{name}")
            if st is not None and hasattr(cb, "ckpt_restore"):
                cb.ckpt_restore(st)
