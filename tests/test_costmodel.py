"""Cost-model tests: roofline arithmetic on a synthetic spec, peak-spec
resolution + the LIGHTGBM_TPU_PEAK_SPECS override, the JitWatch
first-compile HLO capture on CPU, the efficiency join (program costs x
measured phase spans), the ``report costs`` CLI, JSONL trace rotation,
and the bounded xprof capture harness.
"""

import glob
import json
import os
import pathlib

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.obs import costmodel, report
from lightgbm_tpu.obs.compilewatch import JitWatch
from lightgbm_tpu.obs.trace import Tracer
from lightgbm_tpu.utils.log import LightGBMError


# pf/pb chosen so the arithmetic is checkable by hand: ridge AI = 10
SPEC = {"key": "synthetic", "device_kind": "synthetic",
        "flops_per_s": 100.0, "hbm_bytes_per_s": 10.0, "source": "default"}


@pytest.fixture
def global_trace(tmp_path, monkeypatch):
    """Route the process-global tracer to a temp file and isolate the
    process-global cost inventory for one test."""
    from lightgbm_tpu.obs import tracer

    path = str(tmp_path / "trace.jsonl")
    monkeypatch.setenv("LIGHTGBM_TPU_TRACE", path)
    costmodel.reset()
    yield path
    tracer.close()
    tracer.path = None
    tracer.reset_aggregates()
    costmodel.reset()


def _read(path):
    return [json.loads(l) for l in open(path) if l.strip()]


def _toy(n=500, f=5, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = (X[:, 0] + X[:, 1] > 0).astype(float)
    return X, y


class TestRoofline:
    def test_compute_bound_arithmetic(self):
        # work 200 flop / peak 100 flop/s = 2 s; 10 B / 10 B/s = 1 s
        rl = costmodel.roofline(200.0, 10.0, 0.0, SPEC)
        assert rl["bound"] == "compute"
        assert rl["lb_s"] == pytest.approx(2.0)
        assert rl["ai"] == pytest.approx(20.0)
        assert rl["ridge_ai"] == pytest.approx(10.0)

    def test_memory_bound_arithmetic(self):
        rl = costmodel.roofline(10.0, 100.0, 0.0, SPEC)
        assert rl["bound"] == "memory"
        assert rl["lb_s"] == pytest.approx(10.0)
        assert rl["ai"] == pytest.approx(0.1)

    def test_transcendentals_count_as_work(self):
        # 50 transcendentals at 1 flop each: 0.5 s compute vs 0.1 s memory
        rl = costmodel.roofline(0.0, 1.0, 50.0, SPEC)
        assert rl["bound"] == "compute"
        assert rl["lb_s"] == pytest.approx(0.5)

    def test_zero_bytes_means_no_ai(self):
        assert costmodel.roofline(5.0, 0.0, 0.0, SPEC)["ai"] is None


class TestPeakSpecs:
    def test_longest_substring_key_wins(self):
        # "tpu v5 lite" must beat the shorter "tpu v5e"-style keys
        spec = costmodel.resolve_peak_spec("TPU v5 lite")
        assert spec["key"] == "tpu v5 lite"
        assert spec["flops_per_s"] == pytest.approx(197e12)
        assert costmodel.resolve_peak_spec("TPU v4")["key"] == "tpu v4"

    def test_unknown_kind_raises(self):
        # a device that is not in the table is an error, never the cpu row
        with pytest.raises(LightGBMError, match="no such chip"):
            costmodel.resolve_peak_spec("no such chip")

    def test_env_override_merges_and_marks_source(self, monkeypatch):
        monkeypatch.setenv(
            "LIGHTGBM_TPU_PEAK_SPECS",
            '{"cpu": {"flops_per_s": 123.0, "hbm_bytes_per_s": 456.0},'
            ' "tpu v6e": {"flops_per_s": 9e14, "hbm_bytes_per_s": 2e12}}')
        spec = costmodel.resolve_peak_spec("cpu")
        assert spec["flops_per_s"] == pytest.approx(123.0)
        assert spec["hbm_bytes_per_s"] == pytest.approx(456.0)
        assert spec["source"] == "env"
        # brand-new device kinds become matchable
        assert costmodel.resolve_peak_spec("TPU v6e")["key"] == "tpu v6e"

    def test_malformed_env_is_ignored(self, monkeypatch):
        monkeypatch.setenv("LIGHTGBM_TPU_PEAK_SPECS", "{not json")
        spec = costmodel.resolve_peak_spec("cpu")
        assert spec["flops_per_s"] == pytest.approx(
            costmodel.DEFAULT_PEAK_SPECS["cpu"]["flops_per_s"])
        assert spec["source"] == "default"


class TestCaptureOnCpu:
    def test_first_compile_per_signature_emits_jax_cost(
            self, global_trace, monkeypatch):
        import jax
        import jax.numpy as jnp

        from lightgbm_tpu.obs import tracer

        tracer.refresh_from_env()
        # force the deep (compiled) pass regardless of host speed
        monkeypatch.setenv("LIGHTGBM_TPU_COSTMODEL_DEEP_BUDGET", "60")

        def f(a, b):
            return jnp.tanh(a @ b).sum()

        w = JitWatch(jax.jit(f), "test.capture.matmul", phase="test_phase")
        a = jnp.ones((32, 32), jnp.float32)
        w(a, a)
        w(a, a)  # cached signature: must NOT capture again
        b = jnp.ones((16, 16), jnp.float32)
        w(b, b)  # new signature: second capture

        inv = costmodel.inventory()
        assert "test.capture.matmul" in inv
        entry = inv["test.capture.matmul"]
        assert entry["phase"] == "test_phase"
        recs = entry["records"]
        assert len(recs) == 2
        for r in recs:
            assert r["flops"] > 0
            assert r["bytes_accessed"] > 0
            assert r["level"] == "compiled"  # deep pass ran under budget
            assert "temp_bytes" in r
        # the 32x32 matmul does more work than the 16x16 one
        assert recs[0]["flops"] > recs[1]["flops"]

        tracer.close()
        events = [r for r in _read(global_trace)
                  if r.get("ev") == "event" and r.get("name") == "jax_cost"]
        assert len(events) == 2
        assert {e["program"] for e in events} == {"test.capture.matmul"}

    def test_same_program_and_sig_captured_once_per_process(
            self, global_trace):
        """JitWatch instances are rebuilt per trainer: a second watch
        with the same program name and argument signature must NOT
        re-pay the capture (the suite trains many boosters)."""
        import jax
        import jax.numpy as jnp

        from lightgbm_tpu.obs import tracer

        tracer.refresh_from_env()

        def f(a):
            return (a * 2).sum()

        x = jnp.ones((8,), jnp.float32)
        JitWatch(jax.jit(f), "test.capture.dedup", phase="p")(x)
        # fresh watch + fresh jit of a fresh callable: compiles again,
        # but the (program, signature) pair is already captured
        JitWatch(jax.jit(lambda a: (a * 2).sum()),
                 "test.capture.dedup", phase="p")(x)
        recs = costmodel.inventory()["test.capture.dedup"]["records"]
        assert len(recs) == 1

    def test_kill_switch_disables_capture(self, global_trace, monkeypatch):
        import jax
        import jax.numpy as jnp

        from lightgbm_tpu.obs import tracer

        tracer.refresh_from_env()
        monkeypatch.setenv("LIGHTGBM_TPU_COSTMODEL", "0")
        w = JitWatch(jax.jit(lambda x: x * 2), "test.capture.disabled")
        w(jnp.ones((4,)))
        assert "test.capture.disabled" not in costmodel.inventory()

    def test_non_aot_callable_is_skipped(self):
        class W:
            name = "test.capture.nolower"
            phase = None
            _fn = staticmethod(lambda x: x)

        assert costmodel.capture(W(), (1,), {}, 0.0) is None

    def test_traced_training_populates_inventory_and_joins(
            self, global_trace, monkeypatch):
        """Inventory completeness: a traced training run on the fused
        trainer must yield a cost record for the chunk program, and the
        offline join must put it against the ``chunk_program`` span with
        a next-target pick — the `report costs` acceptance path."""
        monkeypatch.setenv("LIGHTGBM_TPU_PGROW", "force")
        monkeypatch.setenv("LIGHTGBM_TPU_COSTMODEL_DEEP_BUDGET", "60")
        # shape chosen to be unique across the test session so the
        # chunk program sees a fresh signature
        X, y = _toy(613, 6, seed=3)
        lgb.train({"objective": "binary", "num_leaves": 7, "verbose": -1},
                  lgb.Dataset(X, label=y), num_boost_round=3,
                  verbose_eval=False)

        inv = costmodel.inventory()
        chunk = [n for n in inv if n.startswith("ptrainer.chunk(")]
        assert chunk, f"the chunk program has no cost record: {inv.keys()}"
        assert inv[chunk[0]]["phase"] == "chunk_program"

        from lightgbm_tpu.obs import tracer

        tracer.close()
        recs = _read(global_trace)
        assert any(r.get("name") == "jax_cost" and r["program"] == chunk[0]
                   and r["phase"] == "chunk_program" for r in recs)
        summary = costmodel.costs_summary(recs)
        assert summary["n_programs"] >= 1
        rows = summary["table"]
        assert rows, "no joinable phases"
        assert "chunk_program" in {r["phase"] for r in rows}
        for r in rows:
            assert r["calls"] > 0 and r["measured_s"] > 0
            assert r["roofline_s"] >= 0
        assert summary["next_target_line"].startswith("next kernel target:")
        text = costmodel.render_costs(summary)
        assert "program inventory" in text
        assert "next kernel target:" in text


def _cost_rec(program, phase, flops, nbytes, trans=0.0, backend="synthetic"):
    return {"ev": "event", "name": "jax_cost", "program": program,
            "phase": phase, "backend": backend, "level": "compiled",
            "flops": flops, "bytes_accessed": nbytes,
            "transcendentals": trans}


def _span_rec(name, dur):
    return {"ev": "span", "name": name, "dur_s": dur}


class TestEfficiencyJoin:
    def test_join_arithmetic_pinned(self):
        # one program, lb 1 s/call; 4 spans of 2 s -> 50% efficiency
        records = [_cost_rec("p.hist", "histogram", 100.0, 10.0)]
        records += [_span_rec("histogram", 2.0)] * 4
        summary = costmodel.costs_summary(records, spec=SPEC)
        (row,) = summary["table"]
        assert row["calls"] == 4
        assert row["measured_s"] == pytest.approx(8.0)
        assert row["roofline_s"] == pytest.approx(4.0)
        assert row["efficiency_pct"] == pytest.approx(50.0)
        assert row["headroom_s"] == pytest.approx(4.0)
        assert row["share_pct"] == pytest.approx(100.0)
        assert summary["next_target"]["program"] == "p.hist"
        assert "p.hist" in summary["next_target_line"]

    def test_representative_is_largest_roofline(self):
        # two programs tag the same phase: the heavier one represents it
        records = [_cost_rec("p.small", "histogram", 10.0, 1.0),
                   _cost_rec("p.big", "histogram", 1000.0, 10.0),
                   _span_rec("histogram", 30.0)]
        (row,) = costmodel.costs_summary(records, spec=SPEC)["table"]
        assert row["program"] == "p.big"
        assert row["roofline_s"] == pytest.approx(10.0)

    def test_next_target_is_max_headroom_not_max_share(self):
        # A: 10 s wall, 1 s roofline (headroom 9); B: 12 s wall, 11 s
        # roofline (headroom 1) — B has more share, A more headroom
        records = [_cost_rec("p.a", "phase_a", 100.0, 1.0),
                   _cost_rec("p.b", "phase_b", 1100.0, 1.0),
                   _span_rec("phase_a", 10.0),
                   _span_rec("phase_b", 12.0)]
        summary = costmodel.costs_summary(records, spec=SPEC)
        assert summary["next_target"]["phase"] == "phase_a"
        assert "phase_a" in summary["next_target_line"]

    def test_untagged_and_unspanned_programs_do_not_join(self):
        records = [_cost_rec("p.nophase", None, 100.0, 10.0),
                   _cost_rec("p.nospan", "ghost_phase", 100.0, 10.0),
                   _span_rec("unrelated", 1.0)]
        summary = costmodel.costs_summary(records, spec=SPEC)
        assert summary["table"] == []
        assert summary["next_target"] is None
        assert summary["n_programs"] == 2  # still inventoried

    def test_multi_signature_mean(self):
        records = [_cost_rec("p.multi", "h", 100.0, 10.0),
                   _cost_rec("p.multi", "h", 300.0, 30.0)]
        st = costmodel.program_stats(
            costmodel.programs_from_trace(records)["p.multi"], SPEC)
        assert st["signatures"] == 2
        assert st["flops_per_call"] == pytest.approx(200.0)
        assert st["bytes_per_call"] == pytest.approx(20.0)


class TestReportCostsCli:
    def _write_trace(self, tmp_path):
        p = str(tmp_path / "t.jsonl")
        records = [_cost_rec("p.hist", "histogram", 100.0, 10.0)]
        records += [_span_rec("histogram", 2.0)] * 4
        with open(p, "w") as f:
            for r in records:
                f.write(json.dumps(r) + "\n")
        return p

    def test_renders_table_and_target(self, tmp_path, capsys, monkeypatch):
        monkeypatch.setenv(
            "LIGHTGBM_TPU_PEAK_SPECS",
            '{"synthetic": {"flops_per_s": 100, "hbm_bytes_per_s": 10}}')
        assert report.costs_main([self._write_trace(tmp_path)]) == 0
        out = capsys.readouterr().out
        assert "cost-model report" in out
        assert "p.hist" in out
        assert "next kernel target: histogram (p.hist)" in out
        assert "LIGHTGBM_TPU_PEAK_SPECS" in out  # env source is labeled

    def test_json_round_trip(self, tmp_path, capsys, monkeypatch):
        monkeypatch.setenv(
            "LIGHTGBM_TPU_PEAK_SPECS",
            '{"synthetic": {"flops_per_s": 100, "hbm_bytes_per_s": 10}}')
        assert report.costs_main(
            [self._write_trace(tmp_path), "--json"]) == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc["peak_spec"]["key"] == "synthetic"
        (row,) = doc["table"]
        assert row["efficiency_pct"] == pytest.approx(50.0)
        assert doc["next_target_line"].startswith("next kernel target:")

    def test_missing_file_and_usage(self, capsys):
        assert report.costs_main(["/no/such/trace.jsonl"]) == 1
        assert report.costs_main([]) == 2

    def test_unknown_device_is_an_error(self, tmp_path, capsys):
        # the trace's device kind ("synthetic") has no peak-spec row
        assert report.costs_main([self._write_trace(tmp_path)]) == 1
        assert "synthetic" in capsys.readouterr().err

    def test_main_dispatches_costs(self, tmp_path, capsys, monkeypatch):
        monkeypatch.setenv(
            "LIGHTGBM_TPU_PEAK_SPECS",
            '{"synthetic": {"flops_per_s": 100, "hbm_bytes_per_s": 10}}')
        assert report.main(["costs", self._write_trace(tmp_path)]) == 0
        assert "cost-model report" in capsys.readouterr().out


class TestTraceRotation:
    def test_rotation_keeps_tail_in_order(self, tmp_path, monkeypatch):
        # ~4 KiB cap: a few hundred events force several rotations
        monkeypatch.setenv("LIGHTGBM_TPU_TRACE_MAX_MB",
                           str(4096 / (1024 * 1024)))
        path = str(tmp_path / "rot.jsonl")
        tr = Tracer()
        tr.configure(path)
        for i in range(300):
            tr.event("rot.seq", i=i)
        tr.close()

        assert os.path.exists(path + ".1")
        recs = report.load_trace(path, warn=False)
        seqs = [r["i"] for r in recs if r.get("name") == "rot.seq"]
        # older generations were clobbered, but what survives is the
        # contiguous tail, in emission order across the .1/current pair
        assert 0 < len(seqs) < 300
        assert seqs == list(range(seqs[0], 300))
        metas = [r for r in recs if r.get("ev") == "meta"]
        assert any(m.get("rotated") for m in metas)

    def test_no_cap_means_no_rotation(self, tmp_path, monkeypatch):
        monkeypatch.delenv("LIGHTGBM_TPU_TRACE_MAX_MB", raising=False)
        path = str(tmp_path / "flat.jsonl")
        tr = Tracer()
        tr.configure(path)
        for i in range(300):
            tr.event("rot.seq", i=i)
        tr.close()
        assert not os.path.exists(path + ".1")
        seqs = [r["i"] for r in report.load_trace(path, warn=False)
                if r.get("name") == "rot.seq"]
        assert seqs == list(range(300))

    def test_garbage_cap_disables_rotation(self, monkeypatch):
        from lightgbm_tpu.obs.trace import _max_bytes_from_env

        monkeypatch.setenv("LIGHTGBM_TPU_TRACE_MAX_MB", "lots")
        assert _max_bytes_from_env() == 0
        monkeypatch.setenv("LIGHTGBM_TPU_TRACE_MAX_MB", "2")
        assert _max_bytes_from_env() == 2 * 1024 * 1024


class TestXprofHarness:
    def test_env_gate(self, monkeypatch):
        from lightgbm_tpu.utils.profiling import maybe_xprof_capture

        monkeypatch.delenv("LIGHTGBM_TPU_XPROF", raising=False)
        assert maybe_xprof_capture() is None
        monkeypatch.setenv("LIGHTGBM_TPU_XPROF", "/tmp/xp")
        monkeypatch.setenv("LIGHTGBM_TPU_XPROF_ITERS", "2")
        monkeypatch.setenv("LIGHTGBM_TPU_XPROF_SKIP", "3")
        cap = maybe_xprof_capture()
        assert cap is not None and cap.log_dir == "/tmp/xp"
        assert cap.iters == 2 and cap.skip == 3

    def test_skip_window_defers_start(self, tmp_path):
        from lightgbm_tpu.utils.profiling import XprofCapture

        cap = XprofCapture(str(tmp_path / "xp"), skip=2, iters=1)
        cap.on_iter_start()
        assert not cap._active  # still inside the skip window
        cap.on_iter_end()
        cap.on_iter_start()
        assert not cap._active
        cap.on_iter_end()
        # close with nothing in flight is a no-op
        cap.close()
        assert not cap._done

    def test_capture_writes_loadable_xplane(self, tmp_path, global_trace):
        import jax.numpy as jnp

        from lightgbm_tpu.obs import tracer
        from lightgbm_tpu.utils.profiling import XprofCapture

        tracer.refresh_from_env()
        d = str(tmp_path / "xprof")
        cap = XprofCapture(d, skip=0, iters=1)
        cap.on_iter_start()
        assert cap._active
        jnp.ones((64, 64)).sum().block_until_ready()
        cap.on_iter_end()
        assert cap._done and not cap._active
        cap.close()  # idempotent after a completed window

        planes = list(pathlib.Path(d).rglob("*.xplane.pb"))
        assert planes, f"no xplane under {d}: {list(pathlib.Path(d).rglob('*'))}"
        assert planes[0].stat().st_size > 0

        tracer.close()
        evs = [r for r in _read(global_trace)
               if r.get("ev") == "event" and r.get("name") == "xprof.capture"]
        assert len(evs) == 1
        assert evs[0]["iters"] == 1 and evs[0]["dir"] == d
