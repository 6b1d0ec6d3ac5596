"""Hardened multi-host transport: deadlines, retry/backoff, peer-failure
detection, cooperative abort, and collective fault injection.

The reference's socket linker (src/network/linkers_socket.cpp Construct)
retries connects against the machine list under a socket timeout and
fails loudly when a peer never answers.  The JAX replacement had no such
layer: the KV-store allgather blocked 120 s per key with no liveness
signal, the device allgather and ``jax.distributed.initialize`` had no
bound at all — one SIGKILLed rank (or a blackholed link to the
coordinator) stalled every surviving host indefinitely.  This module is
that missing layer:

- **Deadlines.**  Every hardened primitive is bounded by
  ``NetSettings.deadline_s`` (param ``network_timeout``, env
  ``LIGHTGBM_TPU_NET_TIMEOUT``).  Nothing blocks forever.
- **Retry/backoff.**  Transient RPC failures retry on a deterministic
  exponential backoff schedule (``network_retries`` /
  ``LIGHTGBM_TPU_NET_RETRIES``), capped by the deadline budget.
- **Peer liveness.**  Each rank's :class:`HeartbeatWriter` rotates a
  per-rank key under ``ltpu_hb/`` in the distributed KV store (the
  store is write-once, so beats write seq N then delete seq N-1); the
  :class:`PeerWatch` sweeper declares a rank dead when its key set has
  not *changed* for ``stale_after_s`` of **local** observation time —
  no cross-host clock comparison is ever made.
- **Typed failures.**  A dead peer surfaces as :class:`PeerFailureError`
  within ~2x the deadline (wait window + staleness window); a lost or
  wedged collective with live peers surfaces as
  :class:`CollectiveTimeoutError`.  Both carry ``elapsed_s``.
- **Cooperative abort.**  On a peer failure the survivors flush the
  latest checkpoint (``ckpt.manager``) and leave through
  :func:`hard_exit` — the JAX distributed-shutdown atexit barrier blocks
  ~100 s against a dead peer and then kills the process with a fatal
  log, so survivors must bypass interpreter exit.  ``task=train``
  auto-resume then restores bit-identically (docs/ROBUSTNESS.md).
- **Fault injection.**  ``LIGHTGBM_TPU_FAULT=die:N|drop_collective:N|
  delay:ms|delay:ms:after:N`` (optionally gated by
  ``LIGHTGBM_TPU_FAULT_RANK``) is checked at every hardened collective,
  so kill/hang/straggler scenarios are testable on a real subprocess
  matrix (tests/test_net_fault.py).  The ``after:N`` form arms the
  per-collective slowdown only from the N-th call on, so a rank can
  *become* a straggler mid-run; :func:`set_delay_scale` scales every
  injected delay multiplicatively (the GBDT driver ties it to the
  rank's current/initial row-count ratio, modeling a host whose
  per-row compute is slow — so shard rebalancing measurably shrinks
  the injected straggler's iteration time, docs/ROBUSTNESS.md).
"""

from __future__ import annotations

import dataclasses
import os
import signal
import struct
import sys
import threading
import time
import zlib
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from ..obs import tracer
from ..utils.log import Log

_HB_DIR = "ltpu_hb/"
_COLLECT_DIR = "ltpu_collect/"
_CHUNK_DIR = "ltpu_chunk/"

# Epoch-scoped collective uid layout, shared by every issuer of
# kv_gather uids (membership.py namespaces, collect.py gathers): bits
# [EPOCH_SHIFT, EPOCH_SHIFT + EPOCH_BITS) carry the membership epoch,
# the low bits the per-epoch sequence/participant digest, bits above
# the purpose namespace.  Scoping uids by epoch means a collective
# retried after a live-membership resize can never read a stale
# pre-transition payload — the key subtrees are disjoint by
# construction, and the coordinator's commit-time GC can reap a whole
# superseded epoch by its uid field alone.
EPOCH_SHIFT = 40
EPOCH_BITS = 18


def epoch_uid(epoch: int, seq: int, ns: int = 0) -> int:
    """Compose ``ns | epoch-field | seq`` for an epoch-scoped collective."""
    epoch = int(epoch)
    if not 0 <= epoch < (1 << EPOCH_BITS):
        raise ValueError(f"epoch {epoch} outside the uid epoch field")
    return int(ns) | (epoch << EPOCH_SHIFT) | int(seq)


def uid_epoch(uid: int) -> int:
    """The epoch field of an epoch-scoped uid (0 for static-world uids)."""
    return (int(uid) >> EPOCH_SHIFT) & ((1 << EPOCH_BITS) - 1)


def _flight_dump(reason: str, error: Optional[BaseException] = None,
                 **attrs) -> None:
    """Flush the crash flight recorder (obs/flight.py) the moment a
    typed transport failure is about to be raised: the survivor's
    flush-and-exit path then always leaves a ``<trace>.crash.jsonl``
    with the final spans before the failure.  No-op when tracing (and
    therefore the ring) is off; never raises."""
    try:
        from ..obs import flight

        flight.dump(reason, error=error, **attrs)
    except Exception:  # pragma: no cover - dying path must not re-fail
        pass


# ----------------------------------------------------------------------
# error hierarchy
# ----------------------------------------------------------------------
class NetError(RuntimeError):
    """Base of the hardened-transport failures (all are bounded: they
    carry how long the operation waited before giving up)."""

    def __init__(self, msg: str, elapsed_s: float = 0.0):
        super().__init__(msg)
        self.elapsed_s = float(elapsed_s)


class CollectiveTimeoutError(NetError):
    """The deadline budget expired but every peer still looks alive —
    a lost, wedged, or badly skewed collective (or an unreachable
    coordinator during bootstrap)."""


class PeerFailureError(NetError):
    """One or more peer ranks stopped heartbeating (or the coordinator
    process died): the run cannot continue and survivors should flush
    the latest checkpoint and exit for auto-resume."""

    def __init__(self, msg: str, ranks: Sequence[int] = (),
                 elapsed_s: float = 0.0):
        super().__init__(msg, elapsed_s)
        self.ranks = tuple(int(r) for r in ranks)


# ----------------------------------------------------------------------
# settings: defaults < config params < env < explicit configure()
# ----------------------------------------------------------------------
@dataclasses.dataclass
class NetSettings:
    """Deadline/retry knobs for every hardened primitive."""

    deadline_s: float = 120.0      # per-collective wait window
    retries: int = 3               # transient-error retry attempts
    backoff_base_s: float = 0.1    # first backoff; doubles per attempt
    backoff_max_s: float = 5.0     # backoff cap
    heartbeat_interval_s: float = 0.0  # 0 = auto: deadline/4, capped 5 s
    stale_after_s: float = 0.0         # 0 = auto: deadline

    def hb_interval(self) -> float:
        if self.heartbeat_interval_s > 0:
            return self.heartbeat_interval_s
        return min(max(self.deadline_s / 4.0, 0.05), 5.0)

    def stale_after(self) -> float:
        return self.stale_after_s if self.stale_after_s > 0 else self.deadline_s

    def poll_s(self) -> float:
        """KV poll / watchdog tick slice: short enough that liveness
        checks interleave, long enough not to hammer the coordinator."""
        return min(max(self.deadline_s / 16.0, 0.05), 0.5)


_ENV_FIELDS: Dict[str, Tuple[str, type]] = {
    "deadline_s": ("LIGHTGBM_TPU_NET_TIMEOUT", float),
    "retries": ("LIGHTGBM_TPU_NET_RETRIES", int),
    "backoff_base_s": ("LIGHTGBM_TPU_NET_BACKOFF", float),
    "heartbeat_interval_s": ("LIGHTGBM_TPU_NET_HEARTBEAT", float),
    "stale_after_s": ("LIGHTGBM_TPU_NET_STALE_AFTER", float),
}

_CONFIG_FIELDS = {
    "deadline_s": "network_timeout",
    "retries": "network_retries",
    "heartbeat_interval_s": "network_heartbeat_interval",
}

_settings: Optional[NetSettings] = None
_settings_lock = threading.Lock()


def _apply_env(s: NetSettings) -> NetSettings:
    for field, (var, typ) in _ENV_FIELDS.items():
        raw = os.environ.get(var, "").strip()
        if raw:
            try:
                setattr(s, field, typ(float(raw)) if typ is int else typ(raw))
            except ValueError:
                Log.warning("Unparsable %s=%r ignored", var, raw)
    return s


def settings() -> NetSettings:
    """The process-wide net settings (env read once, lazily)."""
    global _settings
    with _settings_lock:
        if _settings is None:
            _settings = _apply_env(NetSettings())
        return _settings


def configure(**kw) -> NetSettings:
    """Explicitly override settings fields (tests / embedding runtimes).
    Wins over both config params and env."""
    s = settings()
    for k, v in kw.items():
        if not hasattr(s, k):
            raise TypeError(f"unknown net setting {k!r}")
        setattr(s, k, v)
    return s


def configure_from_config(config) -> NetSettings:
    """Pull ``network_timeout``/``network_retries``/
    ``network_heartbeat_interval`` from a Config.  Env vars win over
    config params (the deployment launcher owns the env)."""
    s = settings()
    for field, param in _CONFIG_FIELDS.items():
        if os.environ.get(_ENV_FIELDS[field][0], "").strip():
            continue  # env override outranks the param surface
        val = getattr(config, param, None)
        if val is not None and float(val) > 0:
            setattr(s, field, type(getattr(s, field))(val))
    return s


def _reset_for_tests() -> None:
    """Drop cached settings/fault state so env changes take effect."""
    global _settings, _fault_specs, _fault_calls, _delay_scale, _wait_clock_s
    with _settings_lock:
        _settings = None
    with _fault_lock:
        _fault_specs = None
        _fault_calls = 0
    _delay_scale = 1.0
    with _wait_clock_lock:
        _wait_clock_s = 0.0
    _chunks_written.clear()


# ----------------------------------------------------------------------
# retry / backoff
# ----------------------------------------------------------------------
def backoff_schedule(retries: int, base_s: float, max_s: float) -> List[float]:
    """Deterministic exponential backoff: base, 2*base, 4*base, ...
    capped at ``max_s`` — one delay per retry attempt."""
    return [min(base_s * (2.0 ** i), max_s) for i in range(max(retries, 0))]


def retry_call(fn: Callable, what: str, retries: Optional[int] = None,
               deadline_s: Optional[float] = None,
               retry_on=(Exception,)):
    """Call ``fn`` with bounded retries on a backoff schedule.  The
    cumulative elapsed time (attempts + sleeps) never exceeds
    ``deadline_s``; exhaustion raises :class:`CollectiveTimeoutError`
    chaining the last error."""
    s = settings()
    retries = s.retries if retries is None else int(retries)
    deadline = s.deadline_s if deadline_s is None else float(deadline_s)
    delays = backoff_schedule(retries, s.backoff_base_s, s.backoff_max_s)
    t0 = time.monotonic()
    last: Optional[BaseException] = None
    for attempt in range(retries + 1):
        try:
            return fn()
        except retry_on as e:  # noqa: PERF203 - retry loop
            last = e
            elapsed = time.monotonic() - t0
            tracer.counter("net.retry", what=what)
            if attempt >= retries or elapsed + delays[attempt] > deadline:
                break
            Log.warning("%s failed (attempt %d/%d): %s — retrying in %.2fs",
                        what, attempt + 1, retries + 1, e, delays[attempt])
            time.sleep(delays[attempt])
    elapsed = time.monotonic() - t0
    tracer.counter("net.timeout", what=what)
    _flight_dump("collective_timeout", error=last, what=what,
                 elapsed_s=round(elapsed, 3))
    raise CollectiveTimeoutError(
        f"{what} failed after {elapsed:.1f}s "
        f"(retries={retries}, deadline={deadline:.0f}s): {last}",
        elapsed_s=elapsed,
    ) from last


# ----------------------------------------------------------------------
# fault injection (tests / chaos drills)
# ----------------------------------------------------------------------
_fault_specs: Optional[List[Tuple]] = None
_fault_calls = 0
_fault_lock = threading.Lock()
# multiplicative scale on every injected delay sleep.  The GBDT driver
# sets it to (current local rows / initial local rows) under a
# row-sharded learner, so an injected per-collective slowdown models a
# host whose PER-ROW compute is slow: moving rows off the straggler
# shrinks its injected stall proportionally, making shard rebalancing
# measurable on CPU (tests/test_rebalance.py).
_delay_scale = 1.0


def set_delay_scale(scale: float) -> None:
    """Scale injected ``delay`` fault sleeps (no-op without faults)."""
    global _delay_scale
    _delay_scale = max(float(scale), 0.0)


def delay_scale() -> float:
    return _delay_scale


# Cross-host wait time spent inside collective transports this interval.
# collect.allgather_bytes feeds it (transport call only, *after* the
# fault_point so injected straggler stalls land on the straggler's own
# compute side); the rebalance controller drains it once per iteration.
_wait_clock_s = 0.0
_wait_clock_lock = threading.Lock()


def wait_clock_add(seconds: float) -> None:
    """Accumulate collective-transport wait time (rebalance signal)."""
    global _wait_clock_s
    with _wait_clock_lock:
        _wait_clock_s += max(float(seconds), 0.0)


def wait_clock_drain() -> float:
    """Return accumulated transport wait seconds and reset to zero."""
    global _wait_clock_s
    with _wait_clock_lock:
        out = _wait_clock_s
        _wait_clock_s = 0.0
    return out


def parse_fault_spec(spec: str) -> List[Tuple]:
    """``die:N | drop_collective:N | delay:ms | delay:ms:after:N``
    (comma-separable).  ``N`` is the 1-based hardened-collective call
    index; a bare ``delay:ms`` applies to every call, while
    ``delay:ms:after:N`` arms the persistent slowdown only from call N
    on (a rank that becomes a straggler mid-run)."""
    out: List[Tuple] = []
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        fields = part.split(":")
        kind = fields[0].strip().lower()
        if kind not in ("die", "drop_collective", "delay"):
            raise ValueError(f"unknown fault kind {kind!r} in {spec!r}")
        if (kind == "delay" and len(fields) == 4
                and fields[2].strip().lower() == "after"):
            try:
                ms, after = float(fields[1]), float(fields[3])
            except ValueError:
                raise ValueError(f"bad fault argument in {part!r}")
            if after < 1:
                raise ValueError(
                    f"delay:ms:after:N needs a 1-based call index, "
                    f"got {part!r}")
            out.append(("delay_after", ms, after))
            continue
        if len(fields) > 2:
            raise ValueError(f"bad fault argument in {part!r}")
        arg = fields[1] if len(fields) > 1 else ""
        try:
            val = float(arg) if arg else 0.0
        except ValueError:
            raise ValueError(f"bad fault argument in {part!r}")
        if kind in ("die", "drop_collective") and val < 1:
            raise ValueError(f"{kind} needs a 1-based call index, got {part!r}")
        out.append((kind, val))
    return out


def _fault_applies_here() -> bool:
    target = os.environ.get("LIGHTGBM_TPU_FAULT_RANK", "").strip()
    if not target:
        return True
    try:
        import jax

        return int(target) == jax.process_index()
    except Exception:
        return True


def fault_point(kind: str = "collective") -> None:
    """Injection hook at the top of every hardened collective.  Parses
    ``LIGHTGBM_TPU_FAULT`` once; no-op (one dict lookup) when unset."""
    global _fault_specs, _fault_calls
    with _fault_lock:
        if _fault_specs is None:
            spec = os.environ.get("LIGHTGBM_TPU_FAULT", "")
            try:
                _fault_specs = parse_fault_spec(spec) if spec else []
            except ValueError as e:
                Log.warning("Ignoring LIGHTGBM_TPU_FAULT: %s", e)
                _fault_specs = []
        if not _fault_specs or not _fault_applies_here():
            return
        _fault_calls += 1
        calls = _fault_calls
    for spec_item in _fault_specs:
        fkind, arg = spec_item[0], spec_item[1]
        if fkind == "delay":
            time.sleep(arg / 1e3 * _delay_scale)
        elif fkind == "delay_after" and calls >= int(spec_item[2]):
            time.sleep(arg / 1e3 * _delay_scale)
        elif fkind == "die" and calls == int(arg):
            Log.warning("FAULT INJECTION: die at %s call %d", kind, calls)
            sys.stdout.flush()
            os.kill(os.getpid(), signal.SIGKILL)
        elif fkind == "drop_collective" and calls == int(arg):
            # simulate a lost collective from a live process: heartbeats
            # keep beating, this rank never contributes — peers must
            # surface CollectiveTimeoutError, not PeerFailureError
            Log.warning("FAULT INJECTION: dropping %s call %d (wedging)",
                        kind, calls)
            sys.stdout.flush()
            while True:
                time.sleep(3600)


# ----------------------------------------------------------------------
# KV-store plumbing
# ----------------------------------------------------------------------
def _client():
    """The process's coordination-service KV client, or None before
    ``jax.distributed.initialize`` (reading it initializes no backend)."""
    from jax._src import distributed

    return distributed.global_state.client


def require_client():
    client = _client()
    if client is None:
        raise NetError("distributed runtime not initialized (no KV client)")
    return client


def _is_deadline_error(e: BaseException) -> bool:
    return "DEADLINE_EXCEEDED" in str(e)


# ----------------------------------------------------------------------
# chunked KV payloads.  The coordination-service KV store is built for
# small config values; multi-MB blobs (elected-histogram allgathers on
# the XLA:CPU transport, wide-matrix find-bin states) are split across
# framed continuation keys with a per-chunk CRC.  (Quantized training,
# purpose "hist_q", shrinks the histogram blobs 3x — int16 (g,h) planes
# instead of f32 (g,h,cnt) — so wide exchanges often fit in a single
# head value and skip the continuation machinery.)  The head value either
# carries the whole payload (_KV_RAW) or a descriptor + the first chunk
# (_KV_CHUNKED); continuation chunks are written BEFORE the head, so a
# reader that sees the head never waits on a missing chunk — no extra
# synchronization round is needed and program-order GC still holds.
# ----------------------------------------------------------------------
_KV_RAW = b"R"
_KV_CHUNKED = b"C"
_KV_CHUNK_HDR = struct.Struct("<IQ")  # (num_chunks, total_len)
_KV_CHUNK_ENV = "LIGHTGBM_TPU_KV_CHUNK"
_KV_CHUNK_DEFAULT = 4 * 1024 * 1024
# (uid, rank) -> number of continuation keys written (for lazy GC; the
# rank in the key matters only for in-process multi-rank simulations,
# where all ranks share this module)
_chunks_written: Dict[Tuple[int, int], int] = {}


def kv_chunk_limit() -> int:
    """Max payload bytes carried by a single KV value (env-overridable;
    tests shrink it to force chunking on tiny blobs)."""
    raw = os.environ.get(_KV_CHUNK_ENV, "").strip()
    if raw:
        try:
            return max(int(raw), 1)
        except ValueError:
            Log.warning("Unparsable %s=%r ignored", _KV_CHUNK_ENV, raw)
    return _KV_CHUNK_DEFAULT


def _frame_chunk(chunk: bytes) -> bytes:
    return struct.pack("<I", zlib.crc32(chunk) & 0xFFFFFFFF) + chunk


def _unframe_chunk(raw: bytes, what: str, key: str) -> bytes:
    if len(raw) < 4:
        raise NetError(f"{what}: truncated KV chunk at {key}")
    want = struct.unpack("<I", raw[:4])[0]
    chunk = raw[4:]
    got = zlib.crc32(chunk) & 0xFFFFFFFF
    if got != want:
        raise NetError(
            f"{what}: KV chunk CRC mismatch at {key} "
            f"(stored {want:#010x}, computed {got:#010x}) — payload "
            f"corrupted in the coordination store")
    return chunk


def _kv_put_payload(client, uid: int, rank: int, key: str, blob: bytes,
                    deadline: float, what: str) -> None:
    """Write ``blob`` under ``key``, splitting payloads larger than the
    chunk limit across ``ltpu_chunk/`` continuation keys (written first,
    see the protocol note above)."""
    limit = kv_chunk_limit()
    if len(blob) <= limit:
        retry_call(lambda: client.key_value_set_bytes(key, _KV_RAW + blob),
                   what=f"{what}[set uid={uid}]", deadline_s=deadline)
        return
    chunks = [blob[i:i + limit] for i in range(0, len(blob), limit)]
    for i in range(1, len(chunks)):
        ckey = f"{_CHUNK_DIR}{uid}/{rank}/{i}"
        framed = _frame_chunk(chunks[i])
        retry_call(lambda k=ckey, v=framed: client.key_value_set_bytes(k, v),
                   what=f"{what}[set chunk uid={uid}/{i}]",
                   deadline_s=deadline)
    _chunks_written[(uid, rank)] = len(chunks) - 1
    tracer.counter("net.kv_chunk", float(len(chunks) - 1), what=what)
    head = (_KV_CHUNKED
            + _KV_CHUNK_HDR.pack(len(chunks), len(blob))
            + _frame_chunk(chunks[0]))
    retry_call(lambda: client.key_value_set_bytes(key, head),
               what=f"{what}[set uid={uid}]", deadline_s=deadline)


def _kv_read_payload(client, uid: int, r: int, head: bytes, poll_ms: int,
                     budget_left: Callable[[], float],
                     watch: Optional["PeerWatch"], what: str) -> bytes:
    """Decode one rank's head value, fetching continuation chunks if the
    payload was split.  Chunks exist before the head is visible, so the
    bounded gets here only absorb store latency, not peer skew."""
    if head[:1] == _KV_RAW:
        return head[1:]
    if head[:1] != _KV_CHUNKED:
        raise NetError(
            f"{what}: unrecognized KV payload framing {head[:1]!r} from "
            f"rank {r} (version skew between ranks?)")
    nchunks, total = _KV_CHUNK_HDR.unpack_from(head, 1)
    parts = [_unframe_chunk(head[1 + _KV_CHUNK_HDR.size:], what,
                            f"{_COLLECT_DIR}{uid}/{r}")]
    for i in range(1, nchunks):
        key = f"{_CHUNK_DIR}{uid}/{r}/{i}"
        while True:
            left = budget_left()
            if left <= 0:
                if watch is not None:
                    watch.check(what)
                tracer.counter("net.timeout", what=what)
                raise CollectiveTimeoutError(
                    f"{what} uid={uid}: chunk {i}/{nchunks} from rank {r} "
                    f"never appeared within the budget")
            try:
                raw = bytes(client.blocking_key_value_get_bytes(key, poll_ms))
                break
            except Exception as e:
                if not _is_deadline_error(e):
                    raise NetError(
                        f"{what} uid={uid}: KV store error reading chunk "
                        f"{key}: {e}") from e
                if watch is not None:
                    watch.check(what)
        parts.append(_unframe_chunk(raw, what, key))
    blob = b"".join(parts)
    if len(blob) != total:
        raise NetError(
            f"{what} uid={uid}: reassembled payload from rank {r} is "
            f"{len(blob)} bytes, descriptor said {total}")
    return blob


def _gc_chunks(client, uid: int, rank: int) -> None:
    cnt = _chunks_written.pop((uid, rank), 0)
    for i in range(1, cnt + 1):
        try:
            client.key_value_delete(f"{_CHUNK_DIR}{uid}/{rank}/{i}")
        except Exception:  # pragma: no cover - GC is best-effort
            pass


# ----------------------------------------------------------------------
# heartbeats + peer liveness
# ----------------------------------------------------------------------
class HeartbeatWriter:
    """Daemon thread rotating this rank's liveness key.  The KV store is
    write-once, so each beat writes ``ltpu_hb/<rank>/<seq>`` then
    deletes seq-1 (write-then-delete keeps at least one key visible).
    A SIGKILL stops the rotation — that frozen key set IS the death
    signal :class:`PeerWatch` reads."""

    def __init__(self, client, rank: int, interval_s: float):
        self._client = client
        self._rank = int(rank)
        self._interval = float(interval_s)
        self._seq = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name="ltpu-heartbeat", daemon=True
        )

    def start(self) -> None:
        self._beat()  # first beat lands before any collective waits on it
        self._thread.start()

    def _beat(self) -> None:
        self._seq += 1
        self._client.key_value_set(
            f"{_HB_DIR}{self._rank}/{self._seq}", str(self._seq)
        )
        if self._seq > 1:
            try:
                self._client.key_value_delete(
                    f"{_HB_DIR}{self._rank}/{self._seq - 1}"
                )
            except Exception:  # pragma: no cover - GC is best-effort
                pass

    def _run(self) -> None:
        while not self._stop.wait(self._interval):
            try:
                with tracer.span("net.heartbeat", rank=self._rank):
                    self._beat()
            except Exception as e:
                # coordinator unreachable: stop beating quietly; the
                # foreground collective will classify the failure
                Log.debug("heartbeat write failed (stopping): %s", e)
                return

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=2.0)
        try:  # clean exit: remove our keys so peers don't sweep a ghost
            self._client.key_value_delete(f"{_HB_DIR}{self._rank}/")
        except Exception:
            pass


class PeerWatch:
    """Liveness sweeper over the per-rank heartbeat keys.

    Staleness is measured in **local observation time**: a rank is dead
    when its heartbeat key set has not changed for ``stale_after_s``
    since this watch last saw it change — no cross-host clock is read,
    so NTP skew cannot cause false positives."""

    def __init__(self, client, rank: int, nproc: int,
                 stale_after_s: Optional[float] = None,
                 time_fn: Callable[[], float] = time.monotonic):
        self._client = client
        self.rank = int(rank)
        self.nproc = int(nproc)
        self._stale_after = stale_after_s
        self._time = time_fn
        self._lock = threading.Lock()
        # rank -> (last observed key-set state, local time it changed)
        self._seen: Dict[int, Tuple[str, float]] = {}
        self._t_start = time_fn()

    def _states(self) -> Dict[int, str]:
        entries = self._client.key_value_dir_get(_HB_DIR)
        states: Dict[int, List[str]] = {}
        for key, val in entries:
            parts = key.split("/")
            if len(parts) < 2:
                continue
            try:
                r = int(parts[1])
            except ValueError:
                continue
            states.setdefault(r, []).append(f"{parts[-1]}={val}")
        return {r: ";".join(sorted(v)) for r, v in states.items()}

    def ages(self) -> Dict[int, float]:
        """Seconds since each peer's heartbeat state last changed (from
        this process's point of observation)."""
        now = self._time()
        states = self._states()
        out: Dict[int, float] = {}
        with self._lock:
            for r in range(self.nproc):
                if r == self.rank:
                    continue
                cur = states.get(r, "<absent>")
                prev = self._seen.get(r)
                if prev is None or prev[0] != cur:
                    # first sight / changed: alive as of now (a missing
                    # key on first sight baselines at watch start so a
                    # never-started peer still times out)
                    t_mark = self._t_start if (
                        prev is None and cur == "<absent>"
                    ) else now
                    self._seen[r] = (cur, t_mark)
                    out[r] = now - t_mark
                else:
                    out[r] = now - prev[1]
        return out

    def dead_ranks(self) -> List[int]:
        stale = (self._stale_after if self._stale_after is not None
                 else settings().stale_after())
        try:
            ages = self.ages()
        except Exception as e:
            # the KV store itself is gone: the coordinator (rank 0)
            # process died — everything routed through it is dead
            _flight_dump("coordinator_unreachable", error=e)
            raise PeerFailureError(
                f"distributed KV store unreachable (coordinator dead?): {e}",
                ranks=(0,),
            ) from e
        return [r for r, age in sorted(ages.items()) if age > stale]

    def check(self, what: str, elapsed_s: float = 0.0) -> None:
        """Raise :class:`PeerFailureError` if any peer went stale."""
        dead = self.dead_ranks()
        if dead:
            stale = (self._stale_after if self._stale_after is not None
                     else settings().stale_after())
            tracer.event("net.peer_failure", what=what, ranks=dead,
                         elapsed_s=round(elapsed_s, 3))
            _flight_dump("peer_failure", what=what, ranks=list(dead),
                         elapsed_s=round(elapsed_s, 3))
            raise PeerFailureError(
                f"rank(s) {dead} stopped heartbeating during {what} "
                f"(no change for > {stale:.1f}s)",
                ranks=dead, elapsed_s=elapsed_s,
            )


_hb_writer: Optional[HeartbeatWriter] = None
_peer_watch: Optional[PeerWatch] = None
_hb_lock = threading.Lock()


def ensure_heartbeat() -> Optional[PeerWatch]:
    """Start this process's heartbeat writer + peer watch once (no-op
    for single-process runs or before the runtime is initialized).
    Returns the shared :class:`PeerWatch`, if any."""
    global _hb_writer, _peer_watch
    with _hb_lock:
        if _peer_watch is not None:
            return _peer_watch
        client = _client()
        if client is None:
            return None
        import jax

        nproc = jax.process_count()
        if nproc <= 1:
            return None
        rank = jax.process_index()
        s = settings()
        writer = HeartbeatWriter(client, rank, s.hb_interval())
        try:
            writer.start()
        except Exception as e:  # pragma: no cover - store down at start
            Log.warning("Could not start heartbeat writer: %s", e)
            return None
        _hb_writer = writer
        _peer_watch = PeerWatch(client, rank, nproc)
        return _peer_watch


def peer_watch() -> Optional[PeerWatch]:
    return _peer_watch


def stop_heartbeat() -> None:
    """Stop the heartbeat and delete this rank's keys (clean shutdown)."""
    global _hb_writer, _peer_watch
    with _hb_lock:
        if _hb_writer is not None:
            _hb_writer.stop()
        _hb_writer = None
        _peer_watch = None


# ----------------------------------------------------------------------
# bounded primitives
# ----------------------------------------------------------------------
def kv_gather(uid: int, blob: bytes, *, client=None, rank: Optional[int] = None,
              nproc: Optional[int] = None, deadline_s: Optional[float] = None,
              watch: Optional[PeerWatch] = None,
              what: str = "kv_allgather") -> List[bytes]:
    """Deadline-bounded KV-store allgather with liveness classification
    and key GC.

    Budget is ``deadline + stale_after`` (~2x deadline): the wait window
    plus the staleness window a peer death needs to become visible.
    Inside the budget the per-rank blocking get polls in short slices,
    sweeping heartbeats between slices so a dead peer raises
    :class:`PeerFailureError` the moment it goes stale; budget expiry
    with live peers raises :class:`CollectiveTimeoutError`.

    GC: completing gather ``uid`` proves every rank finished gather
    ``uid-1`` (each rank writes its uid key before reading any, and
    collectives run in identical program order), so every rank has read
    this rank's ``uid-1`` key — it is deleted here.  Live KV usage is
    thereby bounded to O(ranks) keys instead of growing per gather."""
    s = settings()
    if client is None:
        client = require_client()
    if rank is None or nproc is None:
        import jax

        rank = jax.process_index() if rank is None else rank
        nproc = jax.process_count() if nproc is None else nproc
    deadline = s.deadline_s if deadline_s is None else float(deadline_s)
    budget = deadline + s.stale_after()
    if watch is None:
        watch = _peer_watch
    poll_ms = max(int(s.poll_s() * 1e3), 10)

    own_key = f"{_COLLECT_DIR}{uid}/{rank}"
    _kv_put_payload(client, uid, rank, own_key, blob, deadline, what)

    t0 = time.monotonic()
    out: List[bytes] = []
    for r in range(nproc):
        if r == rank:
            out.append(blob)
            continue
        key = f"{_COLLECT_DIR}{uid}/{r}"
        misses = 0
        while True:
            elapsed = time.monotonic() - t0
            if elapsed >= budget:
                if watch is not None:
                    watch.check(what, elapsed_s=elapsed)
                tracer.counter("net.timeout", what=what)
                _flight_dump("collective_timeout", what=what,
                             elapsed_s=round(elapsed, 3))
                raise CollectiveTimeoutError(
                    f"{what} uid={uid}: rank {r} never contributed within "
                    f"{budget:.1f}s (deadline={deadline:.1f}s) but peers "
                    f"look alive", elapsed_s=elapsed,
                )
            try:
                head = bytes(client.blocking_key_value_get_bytes(key, poll_ms))
                out.append(_kv_read_payload(
                    client, uid, r, head, poll_ms,
                    lambda: budget - (time.monotonic() - t0), watch, what))
                break
            except Exception as e:
                if not _is_deadline_error(e):
                    misses += 1
                    if misses > s.retries:
                        _flight_dump("coordinator_unreachable", error=e,
                                     what=what)
                        raise PeerFailureError(
                            f"{what} uid={uid}: KV store unreachable "
                            f"(coordinator dead?): {e}",
                            ranks=(0,), elapsed_s=elapsed,
                        ) from e
                    time.sleep(min(backoff_schedule(
                        s.retries, s.backoff_base_s, s.backoff_max_s
                    )[misses - 1], max(budget - elapsed, 0.0)))
                    continue
                if watch is not None:
                    watch.check(what, elapsed_s=time.monotonic() - t0)
    if uid > 0:
        try:
            client.key_value_delete(f"{_COLLECT_DIR}{uid - 1}/{rank}")
            _gc_chunks(client, uid - 1, rank)
            tracer.counter("net.kv_gc")
        except Exception:  # pragma: no cover - GC is best-effort
            pass
    return out


def watchdog_call(fn: Callable, what: str,
                  deadline_s: Optional[float] = None,
                  watch: Optional[PeerWatch] = None):
    """Run a blocking call (device allgather, backend init, distributed
    bootstrap) on a watchdog: the call executes on a daemon worker
    thread while this thread ticks, sweeping peer liveness each slice.
    A stale peer raises :class:`PeerFailureError`; budget expiry raises
    :class:`CollectiveTimeoutError`.  The worker thread cannot be
    cancelled — on timeout it is abandoned (daemon) and the caller is
    expected to abort the process via the cooperative-abort path."""
    s = settings()
    deadline = s.deadline_s if deadline_s is None else float(deadline_s)
    budget = deadline + s.stale_after()
    if watch is None:
        watch = _peer_watch
    box: Dict[str, object] = {}
    done = threading.Event()

    def _runner():
        try:
            box["value"] = fn()
        except BaseException as e:  # noqa: BLE001 - ferried to caller
            box["error"] = e
        finally:
            done.set()

    threading.Thread(target=_runner, name=f"ltpu-net-{what}",
                     daemon=True).start()
    t0 = time.monotonic()
    while not done.wait(s.poll_s()):
        elapsed = time.monotonic() - t0
        if watch is not None:
            watch.check(what, elapsed_s=elapsed)
        if elapsed >= budget:
            tracer.counter("net.timeout", what=what)
            _flight_dump("collective_timeout", what=what,
                         elapsed_s=round(elapsed, 3))
            raise CollectiveTimeoutError(
                f"{what} did not complete within {budget:.1f}s "
                f"(deadline={deadline:.1f}s)", elapsed_s=elapsed,
            )
    if "error" in box:
        raise box["error"]  # type: ignore[misc]
    return box.get("value")


# ----------------------------------------------------------------------
# cooperative abort
# ----------------------------------------------------------------------
def hard_exit(code: int) -> None:
    """Exit WITHOUT running interpreter atexit hooks.

    After a peer death the JAX distributed-shutdown barrier (registered
    atexit) blocks until the coordination service's own ~100 s heartbeat
    timeout and then terminates the process with a fatal log — survivors
    that already flushed their checkpoint must not take that path.
    Flushes the tracer and stdio first, then ``os._exit``."""
    try:
        tracer.close()
    except Exception:
        pass
    try:
        sys.stdout.flush()
        sys.stderr.flush()
    except Exception:
        pass
    os._exit(code)
