"""Unit tests for the hardened transport layer (parallel/net.py):
backoff schedule, retry/deadline accounting, fault-spec parsing, the
bounded KV allgather (classification + lazy key GC), and the
heartbeat/PeerWatch liveness protocol — all against an in-memory fake
KV client, no subprocesses.  The real-subprocess kill matrix lives in
test_net_fault.py."""

import threading
import time

import pytest

from lightgbm_tpu.parallel import net


class FakeClient:
    """In-memory stand-in for jaxlib's DistributedRuntimeClient KV API
    (write-once keys, subtree delete, DEADLINE_EXCEEDED on a missing
    blocking get — the semantics probed on the real client)."""

    def __init__(self):
        self.store = {}
        self.deleted = []
        self.lock = threading.Lock()

    def key_value_set(self, key, val):
        self.key_value_set_bytes(key, val.encode())

    def key_value_set_bytes(self, key, val):
        with self.lock:
            if key in self.store:
                raise RuntimeError(f"ALREADY_EXISTS: Config key {key}")
            self.store[key] = bytes(val)

    def blocking_key_value_get_bytes(self, key, timeout_ms):
        with self.lock:
            if key in self.store:
                return self.store[key]
        time.sleep(timeout_ms / 1e3)
        raise RuntimeError(
            f"DEADLINE_EXCEEDED: GetKeyValue() timed out with key: {key}"
        )

    def key_value_delete(self, key):
        with self.lock:
            self.deleted.append(key)
            if key.endswith("/"):
                for k in [k for k in self.store if k.startswith(key)]:
                    del self.store[k]
            else:
                self.store.pop(key, None)

    def key_value_dir_get(self, prefix):
        with self.lock:
            return [(k, v.decode()) for k, v in sorted(self.store.items())
                    if k.startswith(prefix)]


@pytest.fixture(autouse=True)
def _fresh_settings(monkeypatch):
    for var, _ in net._ENV_FIELDS.values():
        monkeypatch.delenv(var, raising=False)
    monkeypatch.delenv("LIGHTGBM_TPU_FAULT", raising=False)
    monkeypatch.delenv("LIGHTGBM_TPU_FAULT_RANK", raising=False)
    net._reset_for_tests()
    yield
    net._reset_for_tests()


# ----------------------------------------------------------------------
class TestSettings:
    def test_defaults_and_derived(self):
        s = net.settings()
        assert s.deadline_s == 120.0 and s.retries == 3
        assert s.stale_after() == 120.0
        assert s.hb_interval() == 5.0  # deadline/4 capped at 5 s
        assert 0.05 <= s.poll_s() <= 0.5

    def test_env_overrides(self, monkeypatch):
        monkeypatch.setenv("LIGHTGBM_TPU_NET_TIMEOUT", "8")
        monkeypatch.setenv("LIGHTGBM_TPU_NET_RETRIES", "1")
        net._reset_for_tests()
        s = net.settings()
        assert s.deadline_s == 8.0 and s.retries == 1
        assert s.hb_interval() == 2.0 and s.stale_after() == 8.0

    def test_config_param_applies_but_env_wins(self, monkeypatch):
        from lightgbm_tpu.config import Config

        cfg = Config.from_params({"network_timeout": 30, "network_retries": 5})
        net.configure_from_config(cfg)
        assert net.settings().deadline_s == 30.0
        assert net.settings().retries == 5
        monkeypatch.setenv("LIGHTGBM_TPU_NET_TIMEOUT", "7")
        net._reset_for_tests()
        net.configure_from_config(cfg)
        assert net.settings().deadline_s == 7.0  # env outranks the param
        assert net.settings().retries == 5

    def test_config_rejects_bad_values(self):
        from lightgbm_tpu.config import Config
        from lightgbm_tpu.utils.log import LightGBMError

        with pytest.raises(LightGBMError, match="network_timeout"):
            Config.from_params({"network_timeout": 0})
        with pytest.raises(LightGBMError, match="bad_row_policy"):
            Config.from_params({"bad_row_policy": "ignore"})


class TestBackoff:
    def test_schedule_doubles_and_caps(self):
        assert net.backoff_schedule(5, 0.1, 0.4) == [0.1, 0.2, 0.4, 0.4, 0.4]
        assert net.backoff_schedule(0, 0.1, 0.4) == []

    def test_retry_succeeds_after_failures(self):
        net.configure(backoff_base_s=0.001, backoff_max_s=0.002)
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise OSError("transient")
            return "ok"

        assert net.retry_call(flaky, "unit") == "ok"
        assert len(calls) == 3

    def test_exhaustion_raises_typed_timeout_with_cause(self):
        net.configure(retries=2, backoff_base_s=0.001, backoff_max_s=0.002)

        def dead():
            raise OSError("always down")

        with pytest.raises(net.CollectiveTimeoutError) as ei:
            net.retry_call(dead, "unit")
        assert isinstance(ei.value.__cause__, OSError)
        assert ei.value.elapsed_s >= 0.0

    def test_deadline_caps_the_schedule(self):
        net.configure(backoff_base_s=0.2, backoff_max_s=5.0)
        t0 = time.monotonic()
        with pytest.raises(net.CollectiveTimeoutError):
            net.retry_call(lambda: 1 / 0, "unit", retries=50,
                           deadline_s=0.05, retry_on=(ZeroDivisionError,))
        assert time.monotonic() - t0 < 1.0  # gave up well before 50 retries


class TestFaultSpec:
    def test_parse(self):
        assert net.parse_fault_spec("die:3") == [("die", 3.0)]
        assert net.parse_fault_spec("drop_collective:2,delay:25") == [
            ("drop_collective", 2.0), ("delay", 25.0)]

    def test_rejects_unknown_kind_and_bad_args(self):
        with pytest.raises(ValueError, match="unknown fault kind"):
            net.parse_fault_spec("explode:1")
        with pytest.raises(ValueError, match="bad fault argument"):
            net.parse_fault_spec("die:soon")
        with pytest.raises(ValueError, match="1-based"):
            net.parse_fault_spec("die")

    def test_delay_fault_applies(self, monkeypatch):
        monkeypatch.setenv("LIGHTGBM_TPU_FAULT", "delay:30")
        net._reset_for_tests()
        t0 = time.monotonic()
        net.fault_point()
        assert time.monotonic() - t0 >= 0.025

    def test_bad_spec_is_ignored_not_fatal(self, monkeypatch):
        monkeypatch.setenv("LIGHTGBM_TPU_FAULT", "explode:1")
        net._reset_for_tests()
        net.fault_point()  # must not raise


# ----------------------------------------------------------------------
class TestPeerWatch:
    def test_heartbeat_change_resets_age(self):
        c = FakeClient()
        clock = [0.0]
        w = net.PeerWatch(c, rank=0, nproc=2, stale_after_s=5.0,
                          time_fn=lambda: clock[0])
        c.key_value_set("ltpu_hb/1/1", "1")
        assert w.dead_ranks() == []
        clock[0] = 4.0
        assert w.dead_ranks() == []
        clock[0] = 6.0  # key set frozen for > 5 s of observation
        assert w.dead_ranks() == [1]
        c.key_value_delete("ltpu_hb/1/1")  # a beat: rotate the key
        c.key_value_set("ltpu_hb/1/2", "2")
        assert w.dead_ranks() == []  # change observed -> alive again

    def test_never_started_peer_times_out_from_watch_start(self):
        c = FakeClient()
        clock = [0.0]
        w = net.PeerWatch(c, rank=0, nproc=3, stale_after_s=2.0,
                          time_fn=lambda: clock[0])
        assert w.dead_ranks() == []
        clock[0] = 3.0
        assert w.dead_ranks() == [1, 2]

    def test_check_raises_typed_error_with_ranks(self):
        c = FakeClient()
        clock = [0.0]
        w = net.PeerWatch(c, rank=0, nproc=2, stale_after_s=1.0,
                          time_fn=lambda: clock[0])
        clock[0] = 2.0
        with pytest.raises(net.PeerFailureError) as ei:
            w.check("unit", elapsed_s=2.0)
        assert ei.value.ranks == (1,)
        assert ei.value.elapsed_s == 2.0

    def test_unreachable_store_is_coordinator_failure(self):
        class DownClient(FakeClient):
            def key_value_dir_get(self, prefix):
                raise RuntimeError("UNAVAILABLE: socket closed")

        w = net.PeerWatch(DownClient(), rank=1, nproc=2, stale_after_s=1.0)
        with pytest.raises(net.PeerFailureError) as ei:
            w.dead_ranks()
        assert ei.value.ranks == (0,)


class TestHeartbeatWriter:
    def test_rotates_keys_and_cleans_up(self):
        c = FakeClient()
        hb = net.HeartbeatWriter(c, rank=0, interval_s=0.01)
        hb.start()
        time.sleep(0.08)
        hb.stop()
        # always exactly one live key while beating; subtree deleted on stop
        assert not [k for k in c.store if k.startswith("ltpu_hb/0/")]
        assert any(k.endswith("/") for k in c.deleted)


# ----------------------------------------------------------------------
class TestKvGather:
    def test_gather_returns_process_order(self):
        c = FakeClient()
        net.configure(deadline_s=2.0)
        net._kv_put_payload(c, 0, 1, "ltpu_collect/0/1", b"from-rank-1",
                            2.0, "test")
        out = net.kv_gather(0, b"from-rank-0", client=c, rank=0, nproc=2)
        assert out == [b"from-rank-0", b"from-rank-1"]

    def test_empty_blob_roundtrip(self):
        # barrier payloads are b""
        c = FakeClient()
        net.configure(deadline_s=2.0)
        net._kv_put_payload(c, 0, 1, "ltpu_collect/0/1", b"", 2.0, "test")
        assert net.kv_gather(0, b"", client=c, rank=0, nproc=2) == [b"", b""]

    def test_lazy_gc_deletes_own_previous_uid(self):
        c = FakeClient()
        net.configure(deadline_s=2.0)
        net._kv_put_payload(c, 0, 1, "ltpu_collect/0/1", b"x", 2.0, "test")
        net.kv_gather(0, b"a", client=c, rank=0, nproc=2)
        assert "ltpu_collect/0/0" in c.store  # nothing to GC yet
        net._kv_put_payload(c, 1, 1, "ltpu_collect/1/1", b"y", 2.0, "test")
        net.kv_gather(1, b"b", client=c, rank=0, nproc=2)
        # completing uid 1 proves every rank read our uid-0 key
        assert "ltpu_collect/0/0" not in c.store
        assert "ltpu_collect/1/0" in c.store

    def test_dead_peer_classified_within_budget(self):
        c = FakeClient()
        net.configure(deadline_s=0.3, stale_after_s=0.3)
        w = net.PeerWatch(c, rank=0, nproc=2, stale_after_s=0.3)
        t0 = time.monotonic()
        with pytest.raises(net.PeerFailureError) as ei:
            net.kv_gather(0, b"mine", client=c, rank=0, nproc=2, watch=w)
        assert ei.value.ranks == (1,)
        assert time.monotonic() - t0 <= 2 * 0.3 + 0.5

    def test_live_but_silent_peer_is_collective_timeout(self):
        import itertools

        seq = itertools.count()

        class BeatingClient(FakeClient):
            # rank 1's heartbeat state changes every sweep: alive forever
            def key_value_dir_get(self, prefix):
                return [(f"ltpu_hb/1/{next(seq)}", "x")]

        c = BeatingClient()
        net.configure(deadline_s=0.25, stale_after_s=0.25)
        w = net.PeerWatch(c, rank=0, nproc=2, stale_after_s=0.25)
        t0 = time.monotonic()
        with pytest.raises(net.CollectiveTimeoutError) as ei:
            net.kv_gather(0, b"mine", client=c, rank=0, nproc=2, watch=w)
        wall = time.monotonic() - t0
        assert 0.4 <= wall <= 1.5  # ~deadline + stale_after, bounded
        assert ei.value.elapsed_s >= 0.4

    def test_unreachable_store_is_peer_failure_after_retries(self):
        class DownClient(FakeClient):
            def blocking_key_value_get_bytes(self, key, timeout_ms):
                raise RuntimeError("UNAVAILABLE: connection refused")

        c = DownClient()
        net.configure(deadline_s=1.0, retries=1, backoff_base_s=0.001,
                      backoff_max_s=0.002)
        with pytest.raises(net.PeerFailureError) as ei:
            net.kv_gather(0, b"mine", client=c, rank=1, nproc=2)
        assert ei.value.ranks == (0,)


class TestWatchdog:
    def test_passes_value_and_errors_through(self):
        assert net.watchdog_call(lambda: 41 + 1, "unit") == 42
        with pytest.raises(KeyError):
            net.watchdog_call(lambda: {}["missing"], "unit")

    def test_hang_raises_bounded_timeout(self):
        net.configure(deadline_s=0.1, stale_after_s=0.05)
        t0 = time.monotonic()
        with pytest.raises(net.CollectiveTimeoutError):
            net.watchdog_call(lambda: time.sleep(5), "unit")
        assert time.monotonic() - t0 < 1.0

    def test_stale_peer_during_hang_is_peer_failure(self):
        c = FakeClient()
        net.configure(deadline_s=5.0, stale_after_s=0.05)
        c.key_value_set("ltpu_hb/1/1", "1")  # frozen forever
        w = net.PeerWatch(c, rank=0, nproc=2, stale_after_s=0.05)
        with pytest.raises(net.PeerFailureError):
            net.watchdog_call(lambda: time.sleep(5), "unit", watch=w)


# ----------------------------------------------------------------------
class TestErrorHierarchyAndExitCodes:
    def test_hierarchy(self):
        assert issubclass(net.PeerFailureError, net.NetError)
        assert issubclass(net.CollectiveTimeoutError, net.NetError)
        assert issubclass(net.NetError, RuntimeError)

    def test_cli_exit_codes(self):
        from lightgbm_tpu.cli import EXIT_NET_TIMEOUT, EXIT_PEER_FAILURE

        assert EXIT_PEER_FAILURE == 75  # EX_TEMPFAIL: restart auto-resumes
        assert EXIT_NET_TIMEOUT == 74
        assert EXIT_PEER_FAILURE not in (0, 1)  # distinct from config errors

    def test_package_exports(self):
        from lightgbm_tpu import parallel

        assert parallel.PeerFailureError is net.PeerFailureError
        assert parallel.CollectiveTimeoutError is net.CollectiveTimeoutError


# ----------------------------------------------------------------------
class TestChunkedKv:
    """Chunked KV payloads: multi-MB blobs split across framed
    continuation keys with per-chunk CRC (elected-histogram allgathers
    on the XLA:CPU transport exceed single-value comfort zones)."""

    def _gather(self, nproc, payloads, client=None, uid=0):
        c = client if client is not None else FakeClient()
        net.configure(deadline_s=5.0)
        res = {}

        def run(r):
            res[r] = net.kv_gather(uid, payloads[r], client=c, rank=r,
                                   nproc=nproc)

        ts = [threading.Thread(target=run, args=(r,)) for r in range(nproc)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        return res, c

    @pytest.mark.parametrize("size", [1, 1024, 8 * 1024 * 1024])
    def test_roundtrip_sizes(self, size, monkeypatch):
        # 256 KiB chunk limit keeps the 8 MiB leg fast while still
        # forcing a 32-chunk reassembly
        monkeypatch.setenv("LIGHTGBM_TPU_KV_CHUNK", str(256 * 1024))
        payloads = [bytes([r]) * size + bytes([r])  # size+1, rank-tagged
                    for r in range(2)]
        res, _ = self._gather(2, payloads)
        assert res[0] == payloads and res[1] == payloads

    def test_small_payload_stays_single_key(self):
        res, c = self._gather(2, [b"a" * 100, b"b"])
        assert res[0] == [b"a" * 100, b"b"]
        assert not any(k.startswith("ltpu_chunk/") for k in c.store)

    def test_chunk_keys_gced_after_next_gather(self, monkeypatch):
        monkeypatch.setenv("LIGHTGBM_TPU_KV_CHUNK", "64")
        payloads = [b"x" * 500, b"y" * 300]
        res, c = self._gather(2, payloads)
        assert res[1] == payloads
        assert any(k.startswith("ltpu_chunk/0/") for k in c.store)
        res2, _ = self._gather(2, [b"p" * 200, b"q"], client=c, uid=1)
        assert res2[0] == [b"p" * 200, b"q"]
        # completing uid 1 proves every rank read uid 0 -> chunks GC'd
        assert not any(k.startswith("ltpu_chunk/0/") for k in c.store)

    def test_crc_mismatch_is_typed_corruption_error(self, monkeypatch):
        monkeypatch.setenv("LIGHTGBM_TPU_KV_CHUNK", "64")
        c = FakeClient()
        net.configure(deadline_s=2.0)
        net._kv_put_payload(c, 0, 1, "ltpu_collect/0/1", b"z" * 500,
                            2.0, "test")
        key = "ltpu_chunk/0/1/1"
        raw = bytearray(c.store[key])
        raw[-1] ^= 0xFF  # flip a payload byte under the stored CRC
        with c.lock:
            c.store[key] = bytes(raw)
        with pytest.raises(net.NetError, match="CRC mismatch"):
            net.kv_gather(0, b"mine", client=c, rank=0, nproc=2)

    def test_chunk_limit_env_and_default(self, monkeypatch):
        monkeypatch.delenv("LIGHTGBM_TPU_KV_CHUNK", raising=False)
        assert net.kv_chunk_limit() == 4 * 1024 * 1024
        monkeypatch.setenv("LIGHTGBM_TPU_KV_CHUNK", "123")
        assert net.kv_chunk_limit() == 123
        monkeypatch.setenv("LIGHTGBM_TPU_KV_CHUNK", "bogus")
        assert net.kv_chunk_limit() == 4 * 1024 * 1024
