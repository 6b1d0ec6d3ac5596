"""Observability-layer tests: tracer unit behavior (span nesting, JSONL
round-trip, disabled-mode overhead), the report CLI, per-iteration record
schema through real ``engine.train`` runs (mask path and the fused
partitioned path with its amortized records), and the JitWatch retrace
detector.
"""

import json
import os
import re
import time

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.obs import report
from lightgbm_tpu.obs.compilewatch import JitWatch
from lightgbm_tpu.obs.trace import Tracer, _NULL_SPAN


@pytest.fixture
def fresh_tracer(tmp_path):
    tr = Tracer()
    tr.configure(str(tmp_path / "trace.jsonl"))
    yield tr
    tr.close()


@pytest.fixture
def global_trace(tmp_path, monkeypatch):
    """Route the process-global tracer to a temp file for one test, and
    restore the disabled state afterwards."""
    from lightgbm_tpu.obs import tracer

    path = str(tmp_path / "trace.jsonl")
    monkeypatch.setenv("LIGHTGBM_TPU_TRACE", path)
    yield path
    tracer.close()
    tracer.path = None
    tracer.reset_aggregates()


def _read(path):
    return [json.loads(l) for l in open(path) if l.strip()]


class TestTracerUnit:
    def test_span_nesting_and_jsonl_roundtrip(self, fresh_tracer, tmp_path):
        tr = fresh_tracer
        with tr.span("outer", tag="a"):
            with tr.span("inner"):
                pass
            with tr.span("inner"):
                pass
        tr.counter("widgets", 3)
        tr.gauge("temp", 1.5, unit="C")
        tr.event("boom", detail="x")
        tr.close()
        recs = _read(tr.path)
        assert recs[0]["ev"] == "meta" and recs[0]["version"] == 1
        spans = [r for r in recs if r["ev"] == "span"]
        # children close (and are written) before the parent
        assert [s["name"] for s in spans] == ["inner", "inner", "outer"]
        assert all(s["parent"] == "outer" and s["depth"] == 1
                   for s in spans[:2])
        assert spans[2]["parent"] is None and spans[2]["depth"] == 0
        assert spans[2]["tag"] == "a"
        assert all(s["dur_s"] >= 0 for s in spans)
        counter = next(r for r in recs if r["ev"] == "counter")
        assert counter["name"] == "widgets" and counter["value"] == 3
        gauge = next(r for r in recs if r["ev"] == "gauge")
        assert gauge["value"] == 1.5 and gauge["unit"] == "C"
        assert any(r["ev"] == "event" and r["name"] == "boom" for r in recs)

    def test_iteration_record(self, fresh_tracer):
        tr = fresh_tracer
        with tr.iteration(7) as rec:
            with tr.span("histogram"):
                pass
            with tr.span("split"):
                pass
            rec["leaves"] = 31
        tr.close()
        it = next(r for r in _read(tr.path) if r["ev"] == "iter")
        assert it["iter"] == 7 and it["leaves"] == 31
        assert set(it["phases"]) == {"histogram", "split"}
        assert it["wall_s"] >= 0 and "host_rss_mb" in it
        assert "compiles" in it

    def test_disabled_mode_is_noop_and_cheap(self):
        tr = Tracer()
        assert not tr.enabled
        # structural near-zero-overhead proof: the SAME singleton no-op
        # context manager is returned for every disabled span
        assert tr.span("x") is _NULL_SPAN
        assert tr.span("y", attr=1) is _NULL_SPAN
        tr.counter("c")
        tr.gauge("g", 1.0)
        tr.event("e")
        with tr.iteration(0) as rec:
            assert rec is None
        t0 = time.perf_counter()
        for _ in range(100_000):
            with tr.span("hot"):
                pass
        assert time.perf_counter() - t0 < 1.0  # ~µs/op budget, loose

    def test_snapshot_aggregates(self, fresh_tracer):
        tr = fresh_tracer
        for _ in range(3):
            with tr.span("phase_a"):
                pass
        snap = tr.snapshot()
        assert snap["spans"]["phase_a"]["count"] == 3
        assert snap["spans"]["phase_a"]["total_s"] >= 0


class TestReportCli:
    def _make_trace(self, tmp_path):
        tr = Tracer()
        p = str(tmp_path / "t.jsonl")
        tr.configure(p)
        for i in range(4):
            with tr.iteration(i) as rec:
                with tr.span("histogram"):
                    pass
                with tr.span("split"):
                    pass
                rec["leaves"] = 15
        tr.close()
        return p

    def test_report_renders_table(self, tmp_path, capsys):
        from lightgbm_tpu.cli import main

        p = self._make_trace(tmp_path)
        assert main(["report", p]) == 0
        out = capsys.readouterr().out
        assert "run-trace report" in out
        assert "histogram" in out and "split" in out
        assert "iterations: 4" in out
        assert "compiles:" in out

    def test_report_json_mode(self, tmp_path, capsys):
        from lightgbm_tpu.cli import main

        p = self._make_trace(tmp_path)
        assert main(["report", p, "--json"]) == 0
        summary = json.loads(capsys.readouterr().out)
        assert summary["iterations"] == 4
        assert "histogram" in summary["phases"]

    def test_report_tolerates_torn_tail(self, tmp_path):
        p = self._make_trace(tmp_path)
        with open(p, "a") as f:
            f.write('{"ev":"iter","iter":99,"wa')  # killed mid-write
        summary = report.summarize(report.load_trace(p))
        assert summary["iterations"] == 4

    def test_report_missing_file(self, capsys):
        from lightgbm_tpu.cli import main

        assert main(["report", "/nonexistent/trace.jsonl"]) == 1
        assert main(["report"]) == 2


def _toy(n=500, f=5, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = (X[:, 0] + X[:, 1] > 0).astype(float)
    return X, y


class TestEngineTraceSchema:
    def test_mask_path_iteration_records(self, global_trace):
        X, y = _toy()
        lgb.train({"objective": "binary", "num_leaves": 7, "verbose": -1},
                  lgb.Dataset(X, label=y), num_boost_round=5,
                  verbose_eval=False)
        recs = _read(global_trace)
        iters = [r for r in recs if r["ev"] == "iter"]
        assert len(iters) == 5
        for i, r in enumerate(iters):
            assert r["iter"] == i
            assert r["leaves"] > 0 and r["trees"] == 1
            assert r["wall_s"] > 0 and r["host_rss_mb"] > 0
            assert "compiles" in r
            # mask-path phases: the fused grow_tree is one program, so
            # the breakdown is at driver granularity
            assert {"boosting", "tree", "train_score"} <= set(r["phases"])
        assert any(r["ev"] == "event" and r["name"] == "train_begin"
                   for r in recs)

    def test_fused_chunk_amortized_records(self, global_trace, monkeypatch):
        monkeypatch.setenv("LIGHTGBM_TPU_PGROW", "force")
        X, y = _toy(600)
        lgb.train({"objective": "binary", "num_leaves": 7, "verbose": -1},
                  lgb.Dataset(X, label=y), num_boost_round=3,
                  verbose_eval=False)
        recs = _read(global_trace)
        iters = [r for r in recs if r["ev"] == "iter"]
        assert len(iters) == 3
        assert all(r.get("amortized") for r in iters)
        assert all("fused_chunk" in r["phases"] for r in iters)
        # the chunk program itself is spanned and watched
        assert any(r["ev"] == "span" and r["name"] == "chunk_program"
                   for r in recs)


class TestRetraceDetector:
    def test_flags_cache_growth_on_seen_signature(self):
        """The env-var-read-at-trace-time bug class: the jit cache key
        changes while the visible ARRAY signature does not — JitWatch
        must flag the recompile as an unexpected retrace."""
        import jax
        import jax.numpy as jnp

        fn = jax.jit(lambda x, mode: x * mode, static_argnames=("mode",))
        w = JitWatch(fn, name="test.retrace")
        x = jnp.ones((4,))
        w(x, mode=2)
        assert w.compiles == 1 and w.retraces == 0
        w(x, mode=2)  # cache hit
        assert w.compiles == 1
        w(x, mode=3)  # same arrays, new static value -> hidden retrace
        assert w.compiles == 2 and w.retraces == 1

    def test_new_shapes_are_not_retraces(self):
        import jax
        import jax.numpy as jnp

        w = JitWatch(jax.jit(lambda x: x + 1), name="test.shapes")
        w(jnp.ones((3,)))
        w(jnp.ones((5,)))
        assert w.compiles == 2 and w.retraces == 0
        assert len(w._sigs) == 2

    def test_cleared_cache_rewarm_is_not_a_retrace(self):
        """jax.clear_caches() empties every jit cache but the watch's
        seen-signature set used to survive it, so the re-warm of each
        already-seen signature was falsely flagged as a retrace (first
        seen as test-order pollution: a module clearing caches between
        two serve modules sharing model shapes).  A shrunken cache must
        reset the seen set."""
        import jax
        import jax.numpy as jnp

        fn = jax.jit(lambda x, mode: x * mode, static_argnames=("mode",))
        w = JitWatch(fn, name="test.cleared")
        x = jnp.ones((4,))
        w(x, mode=2)
        assert w.compiles == 1 and w.retraces == 0
        jax.clear_caches()
        w(x, mode=2)  # legitimate recompile of a seen signature
        assert w.compiles == 2 and w.retraces == 0
        w(x, mode=3)  # real hidden retrace still detected after a clear
        assert w.retraces == 1

    def test_levelgrow_env_participates_in_program_identity(self,
                                                            monkeypatch):
        """Satellite regression: LIGHTGBM_TPU_LEVELGROW is read at
        trainer construction into PGrowParams (static, part of the jit
        cache key), not at trace time inside the grower."""
        from lightgbm_tpu.ops.pgrow import levelgrow_env_params

        monkeypatch.setenv("LIGHTGBM_TPU_LEVELGROW", "0")
        assert levelgrow_env_params() == {"levelwise": False}
        monkeypatch.setenv("LIGHTGBM_TPU_LEVELGROW", "1")
        assert levelgrow_env_params()["levelwise"] is True

        monkeypatch.setenv("LIGHTGBM_TPU_PGROW", "force")
        X, y = _toy(600)
        params = {"objective": "binary", "num_leaves": 7, "verbose": -1}
        monkeypatch.setenv("LIGHTGBM_TPU_LEVELGROW", "0")
        bst = lgb.train(dict(params), lgb.Dataset(X, label=y),
                        num_boost_round=1, verbose_eval=False)
        assert bst.boosting.ptrainer.params.levelwise is False
        monkeypatch.setenv("LIGHTGBM_TPU_LEVELGROW", "1")
        bst = lgb.train(dict(params), lgb.Dataset(X, label=y),
                        num_boost_round=1, verbose_eval=False)
        assert bst.boosting.ptrainer.params.levelwise is True


class TestDisabledOverheadEndToEnd:
    def test_training_emits_nothing_when_disabled(self, tmp_path,
                                                  monkeypatch):
        """With tracing off the instrumented paths must not write records
        or block dispatch (fence is a no-op)."""
        from lightgbm_tpu.obs import tracer
        from lightgbm_tpu.obs.trace import fence

        monkeypatch.delenv("LIGHTGBM_TPU_TRACE", raising=False)
        tracer.close()
        tracer.path = None
        tracer.refresh_from_env()
        assert not tracer.enabled
        assert fence(None) is None
        X, y = _toy()
        lgb.train({"objective": "binary", "num_leaves": 7, "verbose": -1},
                  lgb.Dataset(X, label=y), num_boost_round=2,
                  verbose_eval=False)
        assert not tracer.enabled and tracer.path is None

    def test_tracing_off_does_zero_tracer_work(self, monkeypatch):
        """The overhead guard (ISSUE 7 satellite): training with tracing
        fully off must not allocate a flight ring nor process a single
        tracer-side record.  Pinned on the tracer WORK COUNTER (every
        emitted/mirrored record increments it), not wall clock, so a
        widened hot path cannot hide in timing noise."""
        from lightgbm_tpu.obs import flight, tracer

        monkeypatch.delenv("LIGHTGBM_TPU_TRACE", raising=False)
        monkeypatch.delenv("LIGHTGBM_TPU_AUDIT", raising=False)
        tracer.close()
        tracer.path = None
        tracer.refresh_from_env()
        work_before = tracer.work_ops
        X, y = _toy()
        for force in ("0", "force"):  # mask path AND the fused path
            monkeypatch.setenv("LIGHTGBM_TPU_PGROW", force)
            lgb.train({"objective": "binary", "num_leaves": 7,
                       "verbose": -1},
                      lgb.Dataset(X, label=y), num_boost_round=2,
                      verbose_eval=False)
        assert tracer.work_ops == work_before, (
            "tracer-side work happened with tracing off")
        assert flight.recorder.ring is None, (
            "flight ring allocated with tracing off")


@pytest.fixture
def fresh_stages(monkeypatch):
    """The process-global tracer with an empty stage list and the sink off,
    whatever earlier tests of this worker kept (the list is bounded)."""
    import collections

    from lightgbm_tpu.obs import tracer
    from lightgbm_tpu.obs.trace import STAGES_MAX

    monkeypatch.delenv("LIGHTGBM_TPU_TRACE", raising=False)
    tracer.close()
    tracer.path = None
    monkeypatch.setattr(tracer, "stages", collections.deque(maxlen=STAGES_MAX))
    yield tracer
    tracer.close()
    tracer.path = None


class TestStages:
    """``tracer.stage``: a span that is kept with the sink off."""

    def test_off_a_stage_is_two_clock_reads_and_one_kept_entry(self, monkeypatch):
        import jax

        made = []
        monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                            lambda *a, **k: made.append(a) or pytest.fail("annotation while off"))
        tr = Tracer()
        t_before, wall_before = time.perf_counter(), time.time()
        with tr.stage("outer", rows=5) as outer:
            with tr.stage("inner"):
                pass
            with tr.span("plain"):  # off: the shared no-op, not on the stack
                with tr.stage("inner"):
                    pass
            outer.attrs["found"] = 2
        assert tr.work_ops == 0 and not made and tr.snapshot()["spans"] == {}
        assert [s["name"] for s in tr.stages] == ["inner", "inner", "outer"]
        inner, _, kept = tr.stages
        assert (inner["parent"], inner["depth"]) == ("outer", 1)
        assert (kept["parent"], kept["depth"], kept["rows"], kept["found"]) == (None, 0, 5, 2)
        assert set(kept) == {"name", "t0", "ts", "dur_s", "depth", "parent", "rows", "found"}
        # t0 on perf_counter's clock (the benchmark's spans'), ts on the wall's
        assert t_before <= kept["t0"] <= inner["t0"] <= time.perf_counter()
        assert wall_before <= inner["ts"] <= kept["ts"] <= time.time() + 1e-3
        assert inner["dur_s"] + tr.stages[1]["dur_s"] <= kept["dur_s"]

    def test_on_a_stage_is_an_ordinary_span_and_is_kept_too(self, fresh_tracer):
        tr = fresh_tracer
        with tr.span("plain"):
            with tr.stage("find_bundles", columns=3):
                pass
        tr.record_stage("program_build", time.perf_counter() - 0.25, 0.25, program="p")
        snap = tr.snapshot()["spans"]
        assert snap["find_bundles"]["count"] == 1 and snap["program_build"]["total_s"] == 0.25
        tr.close()
        spans = {r["name"]: r for r in _read(tr.path) if r["ev"] == "span"}
        assert spans["find_bundles"]["parent"] == "plain" and spans["find_bundles"]["columns"] == 3
        assert spans["program_build"]["dur_s"] == 0.25 and spans["program_build"]["program"] == "p"
        assert [s["name"] for s in tr.stages] == ["find_bundles", "program_build"]
        # a kept entry's depth and parent count stages only: the ordinary span
        # around it is there with the sink on alone, and a reader of
        # ``tracer.stages`` gets the same tree either way
        assert [(s["depth"], s["parent"]) for s in tr.stages] == [(0, None), (0, None)]
        summary = report.summarize(report.load_trace(tr.path))
        assert summary["spans"]["find_bundles"]["count"] == 1

    def test_a_stage_closes_by_the_state_it_opened_in(self, tmp_path, monkeypatch):
        """``GBDT.init`` re-reads the environment INSIDE ``booster_init``: no
        annotation to exit where none was entered, and the record goes where
        the sink is by then; and the other way round."""
        import jax

        entered = []

        class Ann:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                entered.append(self.name)

            def __exit__(self, *exc):
                entered.remove(self.name)

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Ann)
        tr = Tracer()
        path = str(tmp_path / "late.jsonl")
        with tr.stage("booster_init"):
            tr.configure(path)
            with tr.stage("bins_upload"):
                assert entered == ["lgbm:bins_upload"]
        assert entered == []
        with tr.stage("opened_on"):
            tr.close()
        assert entered == []  # the annotation it entered was exited
        names = [r["name"] for r in _read(path) if r["ev"] == "span"]
        assert names == ["bins_upload", "booster_init"]
        assert [s["name"] for s in tr.stages] == ["bins_upload", "booster_init", "opened_on"]

    def test_the_list_is_bounded_and_survives_refresh_and_close(self, tmp_path, monkeypatch):
        from lightgbm_tpu.obs.trace import STAGES_MAX

        tr = Tracer()
        for i in range(STAGES_MAX + 7):
            with tr.stage("s", i=i):
                pass
        assert len(tr.stages) == STAGES_MAX == 512
        # the newest are kept: a process that retrains reads its last Booster
        assert (tr.stages[0]["i"], tr.stages[-1]["i"]) == (7, STAGES_MAX + 6)
        monkeypatch.setenv("LIGHTGBM_TPU_TRACE", str(tmp_path / "t.jsonl"))
        tr.refresh_from_env()
        assert tr.enabled and len(tr.stages) == STAGES_MAX
        tr.close()
        monkeypatch.delenv("LIGHTGBM_TPU_TRACE")
        tr.refresh_from_env()
        assert not tr.enabled and len(tr.stages) == STAGES_MAX

    def test_a_stage_nests_among_the_stages_of_its_own_thread(self):
        """A Booster built in a second thread while this one is inside a
        stage is nobody's child, and leaves nothing on this thread's stack."""
        import threading

        tr = Tracer()

        def other():
            with tr.stage("booster_init"):
                with tr.stage("bins_upload"):
                    pass

        with tr.stage("dataset_construct"):
            t = threading.Thread(target=other)
            t.start()
            t.join()
            with tr.stage("load_binary"):
                pass
        with tr.stage("after"):
            pass
        got = {s["name"]: (s["depth"], s["parent"]) for s in tr.stages}
        assert got == {"bins_upload": (1, "booster_init"), "booster_init": (0, None),
                       "load_binary": (1, "dataset_construct"),
                       "dataset_construct": (0, None), "after": (0, None)}

    def test_program_build_is_kept_when_the_jit_cache_grew(self, fresh_stages):
        import jax
        import jax.numpy as jnp

        w = JitWatch(jax.jit(lambda x: jnp.cumsum(x) - 2), name="test.program_build")
        w(jnp.ones((5,)))
        (built,) = fresh_stages.stages
        assert built["name"] == "program_build" and built["program"] == "test.program_build"
        assert set(built) == {"name", "t0", "ts", "dur_s", "depth", "parent", "program",
                              "backend_s", "cache_hit"}
        assert isinstance(built["cache_hit"], bool)
        # the call's own clock: the back-end compile is inside it, and what is
        # left (trace, lowering, dispatch) is what no compile cache removes
        assert 0 < built["backend_s"] < built["dur_s"]
        for _ in range(3):
            w(jnp.ones((5,)))  # jit cache hits: no stage
        assert len(fresh_stages.stages) == 1
        w(jnp.ones((6,)))
        assert [s["program"] for s in fresh_stages.stages] == ["test.program_build"] * 2
        assert fresh_stages.work_ops == fresh_stages.work_ops and not fresh_stages.enabled

    def test_program_build_is_top_level_under_an_ordinary_span(self, fresh_stages,
                                                               global_trace):
        """Sink on, the trainer's ``chunk_program`` span is around the call
        that builds the program; the kept entry still reads depth 0, as it
        does with the sink off, while the JSONL span nests as spans do."""
        import jax
        import jax.numpy as jnp

        tr = fresh_stages
        tr.refresh_from_env()
        w = JitWatch(jax.jit(lambda x: jnp.sin(x) + 5), name="test.under_a_span")
        with tr.span("chunk_program"):
            w(jnp.ones((3,)))
        tr.close()
        (built,) = tr.stages
        assert (built["name"], built["depth"], built["parent"]) == ("program_build", 0, None)
        by = {r["name"]: r for r in _read(global_trace) if r["ev"] == "span"}
        assert by["program_build"]["parent"] == "chunk_program"

    def test_sink_on_the_stages_arrive_as_spans_and_report_aggregates_them(
            self, fresh_stages, global_trace, monkeypatch):
        monkeypatch.setenv("LIGHTGBM_TPU_PGROW", "force")
        X, y = _toy(600)
        params = {"objective": "binary", "num_leaves": 7, "verbose": -1}
        lgb.train(params, lgb.Dataset(X, label=y, params=params), num_boost_round=2,
                  verbose_eval=False)
        fresh_stages.close()
        recs = _read(global_trace)
        spans = [r for r in recs if r["ev"] == "span"]
        by = {s["name"]: s for s in spans}
        assert {"dataset_construct", "find_bins", "bin_rows", "booster_init", "bins_upload",
                "find_bundles", "pack_matrix", "program_build"} <= set(by)
        assert by["dataset_construct"]["parent"] == "booster_init"
        assert by["find_bins"]["parent"] == by["bin_rows"]["parent"] == "dataset_construct"
        assert by["bins_upload"]["parent"] == by["pack_matrix"]["parent"] == "booster_init"
        assert by["program_build"]["parent"] == "chunk_program"
        # engine.train opens no booster_init of its own: one Booster, one span
        assert sum(s["name"] == "booster_init" for s in spans) == 1
        summary = report.summarize(recs)
        assert summary["spans"]["booster_init"]["count"] == 1
        assert summary["spans"]["program_build"]["total_s"] > 0
        # the kept tree is the sink-off tree: `program_build` under no stage
        kept = {s["name"]: s for s in fresh_stages.stages}
        assert kept["program_build"]["depth"] == 0 and kept["booster_init"]["depth"] == 0
        assert kept["dataset_construct"]["parent"] == "booster_init"


class TestFlightRecorder:
    def test_ring_bounded_and_dump_contents(self, tmp_path, monkeypatch):
        from lightgbm_tpu.obs import flight
        from lightgbm_tpu.obs.trace import Tracer

        monkeypatch.setenv("LIGHTGBM_TPU_FLIGHT_RING", "64")
        tr = Tracer()
        tr.configure(str(tmp_path / "t.jsonl"))
        assert flight.recorder.ring is not None
        assert flight.recorder.ring.maxlen == 64
        for i in range(200):
            tr.event("tick", i=i)
        tr.event("boom", last=True)
        p = flight.recorder.dump("unit_test", error=RuntimeError("x"),
                                 extra=1)
        assert p == str(tmp_path / "t.crash.jsonl")
        recs = _read(p)
        meta = recs[0]
        assert meta["ev"] == "meta" and meta["kind"] == "flight"
        assert meta["reason"] == "unit_test"
        assert meta["error"] == "RuntimeError: x" and meta["extra"] == 1
        # bounded: ring capacity + the meta line, keeping the NEWEST
        assert len(recs) == 65
        assert recs[-1]["name"] == "boom"
        assert all(r["name"] == "tick" and r["i"] >= 136
                   for r in recs[1:-1])
        tr.close()
        assert flight.recorder.ring is None  # deactivated with the tracer
        assert flight.recorder.dump("after_close") is None

    def test_net_failure_dumps_ring(self, tmp_path, monkeypatch):
        """The net.py wiring: a typed PeerFailureError raise flushes the
        ring — the survivor's crash dump contains the final records
        before the failure (here driven through PeerWatch.check with a
        fake KV client)."""
        from lightgbm_tpu.obs import flight, tracer
        from lightgbm_tpu.parallel.net import PeerFailureError, PeerWatch

        monkeypatch.setenv("LIGHTGBM_TPU_TRACE",
                           str(tmp_path / "net.jsonl"))
        tracer.refresh_from_env()
        try:
            with tracer.span("net.heartbeat", rank=0):
                pass

            class DeadKV:
                def key_value_dir_get(self, prefix):
                    return [("ltpu_hb/1/5", "5")]

            clock = {"t": 0.0}
            watch = PeerWatch(DeadKV(), rank=0, nproc=2, stale_after_s=1.0,
                              time_fn=lambda: clock["t"])
            watch.ages()
            clock["t"] = 10.0  # rank 1's key set frozen for 10 s
            with pytest.raises(PeerFailureError):
                watch.check("unit_collective")
        finally:
            crash = str(tmp_path / "net.crash.jsonl")
            found = os.path.exists(crash)
            recs = _read(crash) if found else []
            tracer.close()
            tracer.path = None
        assert found, "typed failure left no crash dump"
        assert recs[0]["reason"] == "peer_failure"
        assert any(r.get("ev") == "span" and r.get("name") == "net.heartbeat"
                   for r in recs)
        assert any(r.get("ev") == "event"
                   and r.get("name") == "net.peer_failure" for r in recs)

    def test_sigusr1_dumps(self, tmp_path, monkeypatch):
        import signal

        from lightgbm_tpu.obs import flight, tracer

        monkeypatch.setenv("LIGHTGBM_TPU_TRACE",
                           str(tmp_path / "s.jsonl"))
        tracer.refresh_from_env()
        try:
            tracer.event("before_signal")
            assert flight.install_signal_handler()
            os.kill(os.getpid(), signal.SIGUSR1)
            crash = str(tmp_path / "s.crash.jsonl")
            recs = _read(crash)
        finally:
            signal.signal(signal.SIGUSR1, signal.SIG_DFL)
            tracer.close()
            tracer.path = None
        assert recs[0]["reason"] == "sigusr1"
        assert any(r.get("name") == "before_signal" for r in recs)


class TestTraceIdentity:
    def test_records_carry_rank_world_run_id(self, tmp_path):
        from lightgbm_tpu.obs.trace import Tracer

        tr = Tracer()
        tr.set_identity(rank=3, world_size=8, run_id="host:1234")
        tr.configure(str(tmp_path / "i.jsonl"))
        with tr.span("histogram"):
            pass
        tr.counter("net.retry")
        tr.close()
        recs = _read(str(tmp_path / "i.jsonl"))
        assert recs, "no records written"
        for r in recs:
            assert r["rank"] == 3 and r["world"] == 8
            assert r["run_id"] == "host:1234"

    def test_single_process_records_stay_clean(self, fresh_tracer):
        tr = fresh_tracer
        tr.event("x")
        tr.close()
        recs = _read(tr.path)
        assert all("rank" not in r and "world" not in r for r in recs)


class TestReportGarbageLines:
    def test_garbage_lines_skip_with_warning(self, tmp_path, capsys):
        """Crash-cut traces: unparsable lines ANYWHERE in the file (not
        just a torn tail) must be skipped with a warning, never raise."""
        p = str(tmp_path / "g.jsonl")
        with open(p, "w") as f:
            f.write('{"ev":"meta","version":1}\n')
            f.write("\x00\x00garbage not json\n")
            f.write('{"ev":"iter","iter":0,"wall_s":0.5,"phases":{}}\n')
            f.write('["not", "an", "object"]\n')
            f.write('{"ev":"iter","iter":1,"wa')  # torn tail
        recs = report.load_trace(p)
        err = capsys.readouterr().err
        assert len(recs) == 2
        assert err.count("warning:") == 3
        assert "skipping" in err
        summary = report.summarize(recs)
        assert summary["iterations"] == 1

    def test_report_cli_survives_garbage(self, tmp_path, capsys):
        from lightgbm_tpu.cli import main

        p = str(tmp_path / "g.jsonl")
        with open(p, "w") as f:
            f.write("not json at all\n")
            f.write('{"ev":"iter","iter":0,"wall_s":0.1,"phases":{}}\n')
        assert main(["report", p]) == 0
        out = capsys.readouterr().out
        assert "iterations: 1" in out


def _make_rank_trace(tmp_path, rank, compute_s, wait_s, iters=3):
    """Synthesize one rank's trace with controlled compute/wait spans."""
    from lightgbm_tpu.obs.trace import Tracer

    tr = Tracer()
    tr.set_identity(rank=rank, world_size=2, run_id="merge:test")
    path = str(tmp_path / f"rank{rank}.jsonl")
    tr.configure(path)
    for i in range(iters):
        with tr.iteration(i):
            with tr.span("histogram"):
                time.sleep(compute_s)
            with tr.span("net.barrier", tag=f"it{i}"):
                with tr.span("net.allgather", transport="kv", bytes=4):
                    time.sleep(wait_s)
    tr.close()
    return path


class TestReportMerge:
    def test_straggler_attribution(self, tmp_path):
        # rank 1 computes 4x longer; rank 0 waits in the barrier
        _make_rank_trace(tmp_path, 0, compute_s=0.01, wait_s=0.04)
        _make_rank_trace(tmp_path, 1, compute_s=0.04, wait_s=0.01)
        by = report.load_rank_traces(
            [str(tmp_path / "rank0.jsonl"), str(tmp_path / "rank1.jsonl")])
        m = report.merge_summary(by)
        assert m["ranks"] == [0, 1]
        assert m["world_size"] == 2
        assert m["run_id"] == "merge:test"
        assert m["aligned_iterations"] == 3
        st = m["straggler"]
        assert st["rank"] == 1
        assert st["slowest_rank_share"] > 0.5
        assert st["slowest_in_iters"] == 3
        # barrier-wait attribution: the FAST rank carries the wait
        assert (m["per_rank"][0]["barrier_wait_s"]
                > m["per_rank"][1]["barrier_wait_s"])
        # nested barrier/allgather must not double count: per-iteration
        # wait can never exceed the iteration wall
        for t in m["timeline"]:
            for r in (0, 1):
                assert t["wait_s"][r] <= t["wall_s"][r] + 1e-9
        # per-phase per-rank timeline includes the compute phase
        assert "histogram" in m["phases"]
        assert m["phases"]["histogram"][1] > m["phases"]["histogram"][0]

    def test_alignment_shrinks_to_common_iterations(self, tmp_path):
        """A rank whose trace was cut short (crash) only contributes the
        iterations every rank completed."""
        _make_rank_trace(tmp_path, 0, 0.005, 0.005, iters=5)
        _make_rank_trace(tmp_path, 1, 0.005, 0.005, iters=3)
        by = report.load_rank_traces(
            [str(tmp_path / "rank0.jsonl"), str(tmp_path / "rank1.jsonl")])
        m = report.merge_summary(by)
        assert m["aligned_iterations"] == 3
        assert m["per_rank"][0]["iterations"] == 5

    def test_merge_cli_renders_and_json(self, tmp_path, capsys):
        from lightgbm_tpu.cli import main

        _make_rank_trace(tmp_path, 0, 0.002, 0.01)
        _make_rank_trace(tmp_path, 1, 0.01, 0.002)
        assert main(["report", "merge", str(tmp_path)]) == 0
        out = capsys.readouterr().out
        assert "cross-rank report" in out
        assert "straggler: rank 1" in out
        assert "barrier wait" in out
        assert main(["report", "merge", str(tmp_path), "--json"]) == 0
        m = json.loads(capsys.readouterr().out)
        assert m["straggler"]["rank"] == 1

    def test_mismatched_run_ids_warn(self, tmp_path, capsys):
        from lightgbm_tpu.obs.trace import Tracer

        for rank, rid in ((0, "run:a"), (1, "run:b")):
            tr = Tracer()
            tr.set_identity(rank=rank, world_size=2, run_id=rid)
            tr.configure(str(tmp_path / f"rank{rank}.jsonl"))
            with tr.iteration(0):
                pass
            tr.close()
        by = report.load_rank_traces(
            [str(tmp_path / "rank0.jsonl"), str(tmp_path / "rank1.jsonl")])
        report.merge_summary(by)
        assert "distinct run_ids" in capsys.readouterr().err


class TestReportDiff:
    def test_identical_and_divergent_and_truncated(self, tmp_path,
                                                   capsys):
        from lightgbm_tpu.cli import main

        a = str(tmp_path / "a.jsonl")
        b = str(tmp_path / "b.jsonl")
        recs = [{"ev": "split", "it": 0, "s": 0, "feat": 3, "gain": 1.5},
                {"ev": "split", "it": 0, "s": 1, "feat": 2, "gain": 0.5},
                {"ev": "tree", "it": 0, "leaves": 3,
                 "values": [0.1, 0.2, 0.3]}]
        with open(a, "w") as f:
            f.writelines(json.dumps(r) + "\n" for r in recs)
        with open(b, "w") as f:
            f.writelines(json.dumps(r) + "\n" for r in recs)
        assert main(["report", "diff", a, b]) == 0
        capsys.readouterr()

        recs2 = [dict(r) for r in recs]
        recs2[1] = dict(recs2[1], feat=7, gain=0.25)
        with open(b, "w") as f:
            f.writelines(json.dumps(r) + "\n" for r in recs2)
        assert main(["report", "diff", a, b]) == 1
        out = capsys.readouterr().out
        assert "diverge at record 1" in out
        assert "feat: a=2  b=7" in out
        assert "gain: a=0.5  b=0.25" in out

        # truncated stream: divergence at the cut
        with open(b, "w") as f:
            f.writelines(json.dumps(r) + "\n" for r in recs[:1])
        assert main(["report", "diff", a, b]) == 1
        assert "ends early" in capsys.readouterr().out

    def test_values_divergence_names_the_leaf(self, tmp_path, capsys):
        from lightgbm_tpu.cli import main

        a = str(tmp_path / "a.jsonl")
        b = str(tmp_path / "b.jsonl")
        ra = {"ev": "tree", "it": 2, "k": 0, "leaves": 3,
              "values": [0.1, 0.2, 0.3]}
        rb = dict(ra, values=[0.1, 0.25, 0.3])
        with open(a, "w") as f:
            f.write(json.dumps(ra) + "\n")
        with open(b, "w") as f:
            f.write(json.dumps(rb) + "\n")
        assert main(["report", "diff", a, b]) == 1
        out = capsys.readouterr().out
        assert "values[1]: a=0.2  b=0.25" in out
        assert "it=2" in out


class TestNameRegistryLint:
    """Span/counter/gauge/event and Prometheus metric names are an
    interface (dashboards, report merge, the bench JSON key on them):
    every literal name emitted from the source must appear in the
    docs/OBSERVABILITY.md name registry."""

    TRACER_PAT = re.compile(
        r'tracer\.(?:span|stage|record_stage|counter|gauge|event)\(\s*[\'"]([A-Za-z0-9_.]+)[\'"]')
    METRIC_PAT = re.compile(
        r'(?:registry|reg)\.(?:labeled_)?(?:counter|gauge|histogram)\(\s*\n?\s*'
        r'[\'"]([A-Za-z0-9_:]+)[\'"]')
    # JitWatch program names are an interface too: the cost model keys
    # its inventory (and `report costs` its efficiency join) on them, so
    # a watched program whose name is missing from the registry silently
    # escapes cost accounting.  The pattern tolerates a positional fn
    # arg with one nested call level (JitWatch(self._build_program(...),
    # name=f"...")) and stops capture at "(" so the f-string chunk names
    # contribute their stable prefix (ptrainer.chunk, ...).
    JITWATCH_PAT = re.compile(
        r'JitWatch\((?:[^()\'"]|\([^()]*\))*?'
        r'(?:name\s*=\s*)?f?[\'"]([A-Za-z0-9_.]+)')

    def _source_names(self):
        import pathlib

        repo = pathlib.Path(__file__).resolve().parent.parent
        names = {}
        files = list((repo / "lightgbm_tpu").rglob("*.py"))
        jitwatch_names = 0
        for p in files:
            src = p.read_text()
            for name in self.TRACER_PAT.findall(src):
                names.setdefault(name, str(p))
            for name in self.METRIC_PAT.findall(src):
                names.setdefault(name, str(p))
            for name in self.JITWATCH_PAT.findall(src):
                names.setdefault(name, str(p))
                jitwatch_names += 1
        assert len(names) > 40, "lint scan found suspiciously few names"
        assert jitwatch_names >= 7, (
            "lint scan found suspiciously few JitWatch constructions — "
            "did the JITWATCH_PAT regex rot?")
        return names, repo

    def test_every_emitted_name_is_documented(self):
        names, repo = self._source_names()
        doc = (repo / "docs" / "OBSERVABILITY.md").read_text()
        missing = {n: f for n, f in names.items() if f"`{n}`" not in doc}
        assert not missing, (
            "emitted observability names missing from the "
            "docs/OBSERVABILITY.md name registry table (names are an "
            f"interface — document them): {missing}")

    def test_lint_catches_an_undocumented_name(self, tmp_path):
        """The lint must actually bite: a name not in the doc table is
        reported missing."""
        doc = "| `documented.name` | span | x | y |"
        names = {"documented.name": "a.py", "brand.new.span": "b.py"}
        missing = {n for n in names if f"`{n}`" not in doc}
        assert missing == {"brand.new.span"}

    def test_jitwatch_pattern_catches_real_construction_shapes(self):
        """JITWATCH_PAT must survive every construction idiom the repo
        uses: positional name, name= kwarg, a nested-call fn argument,
        and the f-string chunk names (capturing their stable prefix)."""
        src = '\n'.join([
            'w = JitWatch(predict_raw, "serve.predict_raw",',
            '             phase="serve_batch")',
            'x = JitWatch(fn, name="test.retrace",',
            '             phase="histogram")',
            'self._progs[k] = JitWatch(',
            '    self._build_program(alloc, bag_on, bag_freq, ff),',
            '    name=f"ptrainer.chunk(bag={int(bag_on)},ff={ff})",',
            ')',
        ])
        got = set(self.JITWATCH_PAT.findall(src))
        assert got == {"serve.predict_raw", "test.retrace",
                       "ptrainer.chunk"}
        # and an undocumented watched program is reported missing
        doc = "| `serve.predict_raw` | program | x | y |"
        missing = {n for n in got if f"`{n}`" not in doc}
        assert missing == {"test.retrace", "ptrainer.chunk"}
