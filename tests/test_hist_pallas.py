"""Parity suite for the packed-bin Pallas histogram kernels
(ops/histogram_pallas.py) against the pure-XLA fallback
(ops/histogram.build_histogram), runnable in interpret mode under tier-1.

Covers the edge shapes the tile machinery can get wrong: bin counts that
are not a multiple of the 128-lane tile, single-feature matrices,
zero-gradient rows, empty/unaligned segments, and the multi-leaf
``hist_segments`` variant (one launch covering every active leaf of a
level).  Also pins the ``tune_fchunk`` autotuner contract — including
that fchunk is bit-INVARIANT (it only groups which cells share a
dot_general, never the per-cell contraction order).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from lightgbm_tpu.ops import histogram_pallas as hp
from lightgbm_tpu.ops import pkernels as pk
from lightgbm_tpu.ops.histogram import build_histogram

INTERP = jax.default_backend() != "tpu"
# interpret-mode bf16 emulation is coarser than the TPU MXU path
TOL = 2e-3 if INTERP else 1e-5


def _data(n, f, b, seed=0, zero_grad_frac=0.0):
    rng = np.random.default_rng(seed)
    bins = rng.integers(0, b, size=(n, f), dtype=np.uint8)
    g = rng.standard_normal(n).astype(np.float32)
    h = np.abs(rng.standard_normal(n)).astype(np.float32)
    sel = (rng.random(n) < 0.85).astype(np.float32)
    if zero_grad_frac:
        z = rng.random(n) < zero_grad_frac
        g[z] = 0.0
        h[z] = 0.0
    return bins, g, h, sel


def _ref(bins, g, h, sel, b, lo, hi):
    return np.asarray(build_histogram(
        jnp.asarray(bins[lo:hi]), jnp.asarray(g[lo:hi]), jnp.asarray(h[lo:hi]),
        jnp.asarray(sel[lo:hi]), b,
    ))


def _relerr(got, want):
    return np.abs(np.asarray(got) - want).max() / max(np.abs(want).max(), 1.0)


class TestHistSegment:
    @pytest.mark.parametrize(
        "n,f,b,lo,hi",
        [
            (4096, 11, 32, 100, 3000),
            (2048, 7, 33, 0, 2048),     # bin count not a tile multiple
            (2048, 5, 63, 17, 1951),    # the bench max_bin shape
            (1024, 1, 32, 3, 1000),     # single feature
            (1024, 3, 17, 0, 7),        # tiny segment, odd bin count
            (1024, 3, 32, 500, 500),    # empty segment
        ],
    )
    def test_matches_xla_fallback(self, n, f, b, lo, hi):
        bins, g, h, sel = _data(n, f, b)
        P = hp.pack_columns(jnp.asarray(bins), jnp.asarray(g), jnp.asarray(h),
                            jnp.asarray(sel))
        got = hp.hist_segment(P, jnp.int32(lo), jnp.int32(hi), f, b,
                              interpret=INTERP)
        want = _ref(bins, g, h, sel, b, lo, hi)
        assert _relerr(got, want) < TOL

    def test_zero_gradient_rows(self):
        n, f, b = 2048, 6, 32
        bins, g, h, sel = _data(n, f, b, seed=5, zero_grad_frac=0.5)
        P = hp.pack_columns(jnp.asarray(bins), jnp.asarray(g), jnp.asarray(h),
                            jnp.asarray(sel))
        got = hp.hist_segment(P, jnp.int32(0), jnp.int32(n), f, b,
                              interpret=INTERP)
        want = _ref(bins, g, h, sel, b, 0, n)
        assert _relerr(got, want) < TOL
        # counts stay ROW counts: zero-gradient selected rows still count
        np.testing.assert_allclose(
            np.asarray(got)[:, :, 2].sum(axis=1), np.full(f, sel.sum()),
            rtol=1e-6)

    def test_pgrow_layout_rows(self):
        """hist_segment on the WPAD-padded pgrow packed matrix via the
        explicit ``rows`` triple — bit-identical to hist_dyn."""
        n, f, b = 3072, 9, 32
        bins, g, h, sel = _data(n, f, b, seed=7)
        lay = pk.PLayout(f)
        P = pk.pack_matrix(bins, lay)
        P = P.at[lay.G, :n].set(jnp.asarray(g.view(np.int32)))
        P = P.at[lay.H, :n].set(jnp.asarray(h.view(np.int32)))
        P = P.at[lay.SEL, :n].set(jnp.asarray(sel.view(np.int32)))
        # trim to a BLK multiple (pack_matrix pads by BLK)
        got = hp.hist_segment(P[:, : n + 1024], jnp.int32(40), jnp.int32(2900),
                              f, b, rows=lay.rows, interpret=INTERP)
        via_dyn = pk.hist_dyn(P, 40, 2860, f, b, rows=lay.rows,
                              interpret=INTERP)
        want = _ref(bins, g, h, sel, b, 40, 2900)
        assert _relerr(got, want) < TOL
        np.testing.assert_allclose(np.asarray(got), np.asarray(via_dyn),
                                   rtol=1e-6, atol=1e-6)


class TestHistSegmentQ:
    """Quantized twin: exact int32 sums of int16 levels (the kernel
    carries them as bf16-exact base-256 digits — Mosaic has no int32
    matmul), extremes of the level range included."""

    @pytest.mark.parametrize("qmax", [15, 16383])
    def test_exact_vs_numpy(self, qmax):
        n, f, b, lo, hi = 4096, 11, 32, 100, 3900
        rng = np.random.default_rng(3)
        bins = rng.integers(0, b, size=(n, f), dtype=np.uint8)
        qg = rng.integers(-qmax, qmax + 1, n).astype(np.int32)
        qh = rng.integers(0, 2 * qmax + 2, n).astype(np.int32)
        qg[:4] = [-qmax, qmax, -qmax - 1, 0]
        sel = (rng.random(n) < 0.8).astype(np.int32)
        P = hp.pack_columns_q(jnp.asarray(bins), jnp.asarray(qg),
                              jnp.asarray(qh), jnp.asarray(sel))
        got = np.asarray(hp.hist_segment_q(P, jnp.int32(lo), jnp.int32(hi),
                                           f, b, interpret=INTERP))
        want = np.zeros((f, b, 3), np.int64)
        for j in range(f):
            for c, v in enumerate((qg * sel, qh * sel, sel)):
                np.add.at(want[j, :, c], bins[lo:hi, j], v[lo:hi])
        np.testing.assert_array_equal(got, want)


class TestHistSegments:
    """Multi-leaf variant: one launch covers all active leaves."""

    def test_matches_per_leaf_bit_identical(self):
        """hist_segments must be BIT-identical to per-segment hist_dyn
        launches (same per-block accumulation order, same fchunk): the
        contract that lets the level path adopt it without moving the
        model."""
        n, f, b = 6000, 11, 32
        bins, g, h, sel = _data(n, f, b, seed=3)
        lay = pk.PLayout(f)
        P = pk.pack_matrix(bins, lay)
        P = P.at[lay.G, :n].set(jnp.asarray(g.view(np.int32)))
        P = P.at[lay.H, :n].set(jnp.asarray(h.view(np.int32)))
        P = P.at[lay.SEL, :n].set(jnp.asarray(sel.view(np.int32)))
        segs = np.array(
            [[0, 1024], [1024, 137], [1161, 0], [1161, 2935], [4096, 1904],
             [0, 0], [0, 0], [0, 0]], np.int32)
        n_active = 5
        got = hp.hist_segments(P, jnp.asarray(segs), jnp.int32(n_active),
                               num_features=f, num_bins=b, rows=lay.rows,
                               smax=8, interpret=INTERP)
        for s in range(n_active):
            lo, cnt = segs[s]
            via_dyn = pk.hist_dyn(P, int(lo), int(cnt), f, b, rows=lay.rows,
                                  interpret=INTERP)
            np.testing.assert_array_equal(np.asarray(got[s]),
                                          np.asarray(via_dyn))
            want = _ref(bins, g, h, sel, b, int(lo), int(lo + cnt))
            assert _relerr(got[s], want) < TOL

    def test_edge_shapes(self):
        """Odd bin count + single feature + zero-gradient rows through
        the multi-leaf path."""
        n, f, b = 2048, 1, 33
        bins, g, h, sel = _data(n, f, b, seed=9, zero_grad_frac=0.4)
        P = hp.pack_columns(jnp.asarray(bins), jnp.asarray(g), jnp.asarray(h),
                            jnp.asarray(sel))
        segs = np.array([[0, 700], [700, 1348], [0, 0], [0, 0]], np.int32)
        got = hp.hist_segments(P, jnp.asarray(segs), jnp.int32(2),
                               num_features=f, num_bins=b, smax=4,
                               interpret=INTERP)
        for s in range(2):
            lo, cnt = segs[s]
            want = _ref(bins, g, h, sel, b, int(lo), int(lo + cnt))
            assert _relerr(got[s], want) < TOL

    def test_pgrow_level_hists_helper(self):
        from lightgbm_tpu.ops.pgrow import PGrowParams, level_hists

        n, f, b = 3000, 7, 16
        bins, g, h, sel = _data(n, f, b, seed=11)
        lay = pk.PLayout(f)
        P = pk.pack_matrix(bins, lay)
        P = P.at[lay.G, :n].set(jnp.asarray(g.view(np.int32)))
        P = P.at[lay.H, :n].set(jnp.asarray(h.view(np.int32)))
        P = P.at[lay.SEL, :n].set(jnp.asarray(sel.view(np.int32)))
        params = PGrowParams(num_leaves=7, num_bins=b, num_features=f,
                             num_rows=n)
        segs = np.array([[0, 1500], [1500, 1500], [0, 0], [0, 0]], np.int32)
        got = level_hists(P, jnp.asarray(segs), jnp.int32(2), params,
                          rows=lay.rows, interpret=INTERP)
        for s in range(2):
            lo, cnt = segs[s]
            want = _ref(bins, g, h, sel, b, int(lo), int(lo + cnt))
            assert _relerr(got[s], want) < TOL


class TestTuneFchunk:
    def test_bounds_and_budget(self):
        for nf in (1, 7, 28, 200):
            for nb in (16, 32, 63, 64, 256):
                f = hp.tune_fchunk(nf, nb)
                assert 1 <= f <= nf
                assert f * nb * hp.BLK * 2 <= 2 * 1024 * 1024 or f == 1
        # crowded-VMEM budget keeps the historical 512-row cap
        assert hp.tune_fchunk(28, 63, max_tile_bytes=1024 * 1024) == 8

    def test_env_override(self, monkeypatch):
        monkeypatch.setenv("LIGHTGBM_TPU_HIST_FCHUNK", "3")
        assert hp.tune_fchunk(28, 63) == 3
        monkeypatch.setenv("LIGHTGBM_TPU_HIST_FCHUNK", "9999")
        assert hp.tune_fchunk(28, 63) == 28  # clamped to F
        monkeypatch.setenv("LIGHTGBM_TPU_HIST_FCHUNK", "junk")
        assert hp.tune_fchunk(28, 63) >= 1  # falls back to the tuner

    def test_prefers_lane_aligned_even_division(self):
        # F=28, B=64: 2 chunks of 14 (14*64=896=7*128) beat the legacy
        # 8/8/8/4 split; the tuner must not pick a ragged-tail width
        f = hp.tune_fchunk(28, 64)
        assert hp.fchunk_cost(28, 64, f) <= hp.fchunk_cost(28, 64, 8)

    def test_fchunk_is_bit_invariant(self, monkeypatch):
        """Different fchunk widths must produce bit-identical histograms
        (each (feature, bin) cell contracts the same BLK lanes in the
        same order regardless of grouping)."""
        n, f, b = 2048, 6, 32
        bins, g, h, sel = _data(n, f, b, seed=13)
        P = hp.pack_columns(jnp.asarray(bins), jnp.asarray(g), jnp.asarray(h),
                            jnp.asarray(sel))
        outs = []
        for width in ("1", "3", "6"):
            monkeypatch.setenv("LIGHTGBM_TPU_HIST_FCHUNK", width)
            jax.clear_caches()  # fchunk is read at trace time
            outs.append(np.asarray(hp.hist_segment(
                P, jnp.int32(0), jnp.int32(n), f, b, interpret=INTERP)))
        monkeypatch.delenv("LIGHTGBM_TPU_HIST_FCHUNK")
        jax.clear_caches()
        np.testing.assert_array_equal(outs[0], outs[1])
        np.testing.assert_array_equal(outs[0], outs[2])
