"""Partitioned grower tests (CPU via Pallas interpret mode).

Covers the dynamic-segment kernels (ops/pkernels.py) against their
XLA/numpy reference implementations, the two-ended partition protocol by
exhaustive host-side simulation, one-tree structural parity between
grow_tree_partitioned and the mask-based grow_tree, and the fused
trainer end-to-end against the default path.
"""

import functools
import os
import random

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from lightgbm_tpu.ops import pkernels as pk
from lightgbm_tpu.ops.pgrow import (
    PGrowParams,
    grow_tree_partitioned,
    leaf_id_from_segments,
    segment_values,
)

INTERP = jax.default_backend() != "tpu"


def _make_packed(n=6000, f=11, b=32, seed=7, weights=False):
    rng = np.random.default_rng(seed)
    lay = pk.PLayout(f)
    bins = rng.integers(0, b, size=(n, f), dtype=np.uint8)
    label = rng.random(n).astype(np.float32)
    P = pk.pack_matrix(bins, lay, label=label,
                       weight=rng.random(n).astype(np.float32) if weights else None)
    g = rng.standard_normal(n).astype(np.float32)
    h = np.abs(rng.standard_normal(n)).astype(np.float32)
    sel = (rng.random(n) < 0.85).astype(np.float32)
    P = P.at[lay.G, :n].set(jnp.asarray(g.view(np.int32)))
    P = P.at[lay.H, :n].set(jnp.asarray(h.view(np.int32)))
    P = P.at[lay.SEL, :n].set(jnp.asarray(sel.view(np.int32)))
    return P, lay, bins, g, h, sel


class TestHistKernel:
    @pytest.mark.parametrize("start,cnt", [(0, 6000), (123, 3000), (7, 77), (5990, 10)])
    def test_matches_reference(self, start, cnt):
        P, lay, *_ = _make_packed()
        hd = np.asarray(pk.hist_dyn(P, start, cnt, lay.F, 32, rows=lay.rows,
                                    interpret=INTERP))
        hr = np.asarray(pk.hist_ref(P, start, cnt, lay, 32))
        err = np.abs(hd - hr).max() / max(np.abs(hr).max(), 1.0)
        # interpret-mode bf16 emulation is coarser than the TPU MXU path
        assert err < (2e-3 if INTERP else 1e-5)


def _child_hists(planes, f, b):
    """``split_stream``'s six planes laid out as (left, right), each (F, B, 3):
    the form ``hist_ref`` and ``hist_dyn`` give."""
    return (pk._hist_cells(*planes[0:3], f, b), pk._hist_cells(*planes[3:6], f, b))


def _check_split_stream(P, lay, start, cnt, feat, thr, zb, dbz, cat, bits=8,
                        nbins=32):
    """split_stream vs the stable numpy reference: same left/right row
    SETS (sorted by the rowid channel — the kernel is unordered within a
    side), every channel traveling with its row, untouched columns
    outside the segment, and both returned histograms matching hist_ref
    on the reference-partitioned children."""
    per = 32 // bits
    # reference FIRST: split_stream donates its input buffer (the jit
    # wrapper carries donate_argnums), so P must not be read afterwards —
    # pass a copy so callers can reuse P across checks
    Pref, nlref = pk.partition_ref(P, start, cnt, feat, zb, dbz, thr, bool(cat), lay)
    P2, nl, planes = pk.split_stream(
        jnp.array(P), start, cnt, feat // per, (feat % per) * bits, zb, dbz,
        thr, cat,
        num_features=lay.F, num_bins=nbins, bits=bits, rows=lay.rows,
        interpret=INTERP,
    )
    assert planes.shape == (6, pk.hist_lanes(lay.F, nbins))
    lh, rh = _child_hists(planes, lay.F, nbins)
    assert int(nl) == nlref
    P2n, Prefn = np.asarray(P2), np.asarray(Pref)
    # outside the segment: bit-identical
    np.testing.assert_array_equal(P2n[:, :start], Prefn[:, :start])
    np.testing.assert_array_equal(P2n[:, start + cnt:], Prefn[:, start + cnt:])

    def canon(mat, lo, hi):
        seg = mat[:, lo:hi]
        order = np.argsort(seg[lay.ROWID], kind="stable")
        return seg[:, order]

    # each side holds the same rows (all channels) as the stable reference
    np.testing.assert_array_equal(
        canon(P2n, start, start + nlref), canon(Prefn, start, start + nlref))
    np.testing.assert_array_equal(
        canon(P2n, start + nlref, start + cnt), canon(Prefn, start + nlref, start + cnt))
    # histograms of both children from the same pass
    tol = 2e-3 if INTERP else 1e-5
    for hist, lo, hi in ((lh, start, start + nlref), (rh, start + nlref, start + cnt)):
        hrf = np.asarray(pk.hist_ref(Pref, lo, hi - lo, lay, nbins))
        err = np.abs(np.asarray(hist) - hrf).max() / max(np.abs(hrf).max(), 1.0)
        assert err < tol


class TestSplitStreamKernel:
    @pytest.mark.parametrize(
        "start,cnt,feat,thr,zb,dbz,cat",
        [
            (0, 6000, 3, 15, 0, 0, 0),
            (123, 3000, 0, 7, 5, 11, 0),   # zero-bin remap
            (1111, 2222, 10, 4, 0, 0, 1),  # categorical (== thr)
            (7, 137, 7, 15, 0, 0, 0),      # tiny unaligned segment
            (2048, 1024, 2, 9, 0, 0, 0),   # exactly block-aligned
            (4000, 900, 1, 0, 0, 0, 0),    # all-or-nothing thresholds
            (4000, 900, 1, 31, 0, 0, 0),
        ],
    )
    def test_matches_reference(self, start, cnt, feat, thr, zb, dbz, cat):
        P, lay, *_ = _make_packed()
        _check_split_stream(P, lay, start, cnt, feat, thr, zb, dbz, cat)

    def test_randomized_segments(self):
        P, lay, *_ = _make_packed(n=9000)
        rng = random.Random(3)
        for _ in range(6):
            cnt = rng.randrange(2, 8000)
            start = rng.randrange(0, 9000 - cnt)
            _check_split_stream(P, lay, start, cnt, rng.randrange(0, lay.F),
                                rng.randrange(0, 31), 0, 0, 0)


class TestLevelStreamKernel:
    """level_stream (one launch, many segments) must reproduce
    split_stream segment-for-segment: same left counts, same children
    histograms, and the identical in-place partition — including empty,
    tiny-unaligned, and block-aligned segments in one call."""

    def test_matches_split_stream_per_segment(self):
        P, lay, *_ = _make_packed(n=6000)
        F, B = lay.F, 32
        per = 32 // lay.bits
        # disjoint segments covering assorted shapes (cnt=0 is a leaf the
        # level pass must pass through untouched)
        segs = [
            (0, 1024, 3, 15, 0, 0, 0),
            (1024, 0, 0, 7, 0, 0, 0),       # empty, block-aligned start
            (1024, 137, 0, 7, 5, 11, 0),    # tiny + zero-bin remap
            (1161, 2935, 10, 4, 0, 0, 1),   # categorical
            (4096, 1904, 7, 20, 0, 0, 0),
        ]
        smax = 8
        tab = np.zeros((smax, 12), np.int32)
        for i, (s, c, f, t, zb, dbz, cat) in enumerate(segs):
            tab[i] = [s, c, f // per, (f % per) * lay.bits, zb, dbz, t, cat,
                      0, 1 << lay.bits, 0, 0]
        # level_stream donates its input: hand it a copy, the per-segment
        # split_stream chain below still consumes the original P
        pl_, nl, hists = pk.level_stream(
            jnp.array(P), jnp.asarray(tab), jnp.int32(len(segs)), num_features=F,
            num_bins=B, bits=lay.bits, rows=lay.rows, smax=smax,
            interpret=INTERP,
        )
        pl_ = np.asarray(pl_)
        nl = np.asarray(nl)
        hists = np.asarray(hists)

        ps = P
        for i, (s, c, f, t, zb, dbz, cat) in enumerate(segs):
            ps, nls, planes = pk.split_stream(
                ps, s, c, f // per, (f % per) * lay.bits, zb, dbz, t, cat,
                num_features=F, num_bins=B, bits=lay.bits, rows=lay.rows,
                interpret=INTERP,
            )
            lh, rh = _child_hists(planes, F, B)
            assert int(nls) == int(nl[i]), f"seg {i} left count"
            ll = np.asarray(pk._hist_from_rows(jnp.asarray(hists[i]), F, B, row0=0))
            rr = np.asarray(pk._hist_from_rows(jnp.asarray(hists[i]), F, B, row0=7))
            tol = 2e-3 if INTERP else 1e-5
            for got, want in ((ll, np.asarray(lh)), (rr, np.asarray(rh))):
                err = np.abs(got - want).max() / max(np.abs(want).max(), 1.0)
                assert err < tol, f"seg {i} hist mismatch {err}"
        # identical in-place partition (same protocol, same block order)
        np.testing.assert_array_equal(pl_, np.asarray(ps))

    def test_zero_active_is_noop(self):
        P, lay, *_ = _make_packed(n=3000)
        Pn = np.asarray(P)  # snapshot: level_stream donates its input
        tab = jnp.zeros((8, 12), jnp.int32)
        pl_, nl, _ = pk.level_stream(
            P, tab, jnp.int32(0), num_features=lay.F, num_bins=32,
            bits=lay.bits, rows=lay.rows, smax=8, interpret=INTERP,
        )
        np.testing.assert_array_equal(np.asarray(pl_), Pn)


def _dense_compaction(block, gl, gr, cl, cr):
    """The formula the staircase replaced: the (BLK, BLK) one-hots
    ``ii == cl + cumsum(gl) - 1`` and ``ii == BLK - cr - cumsum(gr)`` (mod
    BLK), applied as the scatter they are.  (permL, permR, cntl, cntr)."""
    B = pk.BLK
    permL, permR = np.zeros_like(block), np.zeros_like(block)
    tgtL = (cl + np.cumsum(gl) - 1) % B
    tgtR = (B - cr - np.cumsum(gr)) % B
    permL[:, tgtL[gl]] = block[:, gl]
    permR[:, tgtR[gr]] = block[:, gr]
    return permL, permR, int(gl.sum()), int(gr.sum())


@functools.partial(jax.jit, static_argnames=("interpret",))
def _compaction_alone(block, masks, fills, interpret):
    """``pk._staircase`` and ``pk._apply_staircase`` on ONE (C, BLK) block, as
    ``_run_segment`` calls them: masks (2, BLK) f32 0/1 (left, right), fills
    (2,) int32 (cl, cr) -> permL, permR (C, BLK), counts (2,)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    c = block.shape[0]

    def kernel(fills_ref, blk_ref, m_ref, outL, outR, cnt_ref, tri, oh, pacc):
        pk._build_tri(tri)
        cntl, cntr, win = pk._staircase(m_ref[0:1, :], m_ref[1:2, :], fills_ref[0],
                                        fills_ref[1], tri, oh)
        cnt_ref[0] = cntl
        cnt_ref[1] = cntr

        def permute(rws):
            outL[rws, :], outR[rws, :] = pk._apply_staircase(blk_ref[rws, :], oh, pacc, win)

        pk._for_row_groups(c, permute)

    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(1,), in_specs=[vmem, vmem],
            out_specs=[vmem, vmem, pl.BlockSpec(memory_space=pltpu.SMEM)],
            scratch_shapes=pk._compaction_scratch(c)),
        out_shape=(jax.ShapeDtypeStruct(block.shape, jnp.int32),
                   jax.ShapeDtypeStruct(block.shape, jnp.int32),
                   jax.ShapeDtypeStruct((2,), jnp.int32)),
        interpret=interpret,
    )(fills, block, masks)


def _dense_segment(P, start, cnt, goes_left):
    """``pk._run_segment``'s two-ended protocol replayed on the host with the
    dense compaction: the matrix split_stream must leave, BIT FOR BIT (which
    block is in hand, and so the order of rows within a side, is the
    protocol's; ``partition_ref`` knows only the row sets).  A read lands
    when it is waited for, a write when it is started: the protocol lets no
    write touch a block between those two (TestTwoEndProtocol).
    ``goes_left`` maps a (C, BLK) block to its (BLK,) bool flags."""
    B, RING = pk.BLK, pk._RING
    P = np.array(P)
    base = start // B * B
    head = start - base
    E = head + cnt
    nblk = -(-E // B)
    lane = np.arange(B)

    def blk(k):
        return P[:, base + k * B: base + (k + 1) * B]

    carL, carR = blk(0).copy(), blk(max(nblk - 1, 0)).copy()
    bufF, bufB = {}, {}
    if_ = ib = cf = cb = kf = kb = fl = fr = 0
    cl, cr = head, nblk * B - E

    def waitF():
        nonlocal cf
        bufF[cf % RING] = blk(cf).copy()
        cf += 1

    def waitB():
        nonlocal cb
        bufB[cb % RING] = blk(nblk - 1 - cb).copy()
        cb += 1

    for _ in range(nblk):
        budget = if_ + ib < nblk
        if cf - fl == 0 and (if_ > cf or budget):
            if_ += if_ == cf
            waitF()
        budget = if_ + ib < nblk
        if cb - fr == 0 and (ib > cb or budget):
            ib += ib == cb
            waitB()
        budget = if_ + ib < nblk
        if cf - kf == 0 and cb - kb == 0:
            if if_ > cf or budget:
                if_ += if_ == cf
                waitF()
            else:
                ib += ib == cb
                waitB()
        useF = cf - kf > 0
        hand = bufF[kf % RING] if useF else bufB[kb % RING]
        jh = kf if useF else nblk - 1 - kb
        kf, kb = kf + useF, kb + (not useF)
        pos = lane + jh * B
        valid = (pos >= head) & (pos < E)
        gl = goes_left(hand) & valid
        gr = valid & ~gl
        permL, permR, cntl, cntr = _dense_compaction(hand, gl, gr, cl, cr)
        tL, tR = cl + cntl, cr + cntr
        flushL, flushR = tL >= B, tR >= B
        rtgt = nblk - 1 - fr
        if flushL and ib > cb and fl == nblk - 1 - cb:
            waitB()
        if flushL and if_ > cf and fl == cf:
            waitF()
        if flushR and ib > cb and rtgt == nblk - 1 - cb:
            waitB()
        if flushR and if_ > cf and rtgt == cf:
            waitF()
        mergedL = np.where(lane < cl, carL, permL)
        mergedR = np.where(lane >= B - cr, carR, permR)
        if flushL:
            blk(fl)[:] = mergedL
        if flushR:
            blk(rtgt)[:] = mergedR
        carL = permL if flushL else mergedL
        carR = permR if flushR else mergedR
        cl, fl = (tL - B, fl + 1) if flushL else (tL, fl)
        cr, fr = (tR - B, fr + 1) if flushR else (tR, fr)
        budget = if_ + ib < nblk
        if_ += budget and useF and if_ - kf < RING
        budget = if_ + ib < nblk
        ib += budget and (not useF) and ib - kb < RING
    assert cl + cr in (0, B)
    if cl + cr:
        blk(fl)[:] = np.where(lane < cl, carL, carR)
    return P, fl * B + cl - head


class TestStaircaseCompaction:
    """The in-block compaction (PR 37): per source lane tile a two-tile
    window of the one-hot, where there was a (BLK, BLK) one-hot a side."""

    @staticmethod
    def _block(c, seed):
        rng = np.random.default_rng(seed)
        return rng.integers(-2**31, 2**31, size=(c, pk.BLK), dtype=np.int64).astype(np.int32)

    # every fill of {0, 1, 127, 128, 129, 1023} on either side; 960 and 1023
    # start a window in tile 7 that wraps into tile 0
    @pytest.mark.parametrize("c", [16, 136], ids=["C16", "C136-row-groups"])
    @pytest.mark.parametrize("lefts", [0, 1, 512, 1023, 1024],
                             ids=["share0", "share1of1024", "half", "share1023of1024", "share1"])
    @pytest.mark.parametrize("cl,cr", [(0, 1023), (1, 129), (127, 128), (128, 127), (129, 1),
                                       (1023, 0), (960, 960)])
    def test_alone_against_the_dense_one_hots(self, c, lefts, cl, cr):
        rng = np.random.default_rng(1000 * cl + cr + lefts)
        block = self._block(c, lefts + c)
        gl = np.zeros(pk.BLK, bool)
        gl[rng.choice(pk.BLK, size=lefts, replace=False)] = True
        # the half-and-half case also has lanes that go nowhere (a head or a
        # tail outside the segment); the extreme shares are exact
        valid = rng.random(pk.BLK) < 0.9 if lefts == 512 else np.ones(pk.BLK, bool)
        gl &= valid
        gr = valid & ~gl
        got = _compaction_alone(jnp.asarray(block), jnp.asarray(np.stack([gl, gr]), jnp.float32),
                                jnp.asarray([cl, cr], jnp.int32), interpret=INTERP)
        wantL, wantR, cntl, cntr = _dense_compaction(block, gl, gr, cl, cr)
        assert np.asarray(got[2]).tolist() == [cntl, cntr]
        np.testing.assert_array_equal(np.asarray(got[0]), wantL)
        np.testing.assert_array_equal(np.asarray(got[1]), wantR)

    # (head, lanes past the tail): the fills the first block starts with
    @pytest.mark.parametrize("thr,cat", [(40, 1), (0, 0), (15, 0), (31, 0)],
                             ids=["none-left", "1-in-32-left", "half-left", "all-left"])
    @pytest.mark.parametrize("head,tail", [(1, 1023), (127, 129), (129, 127), (1023, 1), (0, 0)])
    def test_segment_bit_for_bit(self, head, tail, thr, cat):
        """split_stream on a segment whose head and tail are not
        block-aligned: the matrix and ``nl`` of the dense kernel, replayed on
        the host, and ``partition_ref``'s row sets."""
        P, lay, *_ = _make_packed(n=7000)
        start = 1024 + head
        cnt = 5 * 1024 - head - tail
        feat, per = 3, 32 // lay.bits

        def goes_left(block):
            b = (block[feat // per] >> ((feat % per) * lay.bits)) & 255
            return (b == thr) if cat else (b <= thr)

        want, nl_want = _dense_segment(P, start, cnt, goes_left)
        _check_split_stream(P, lay, start, cnt, feat, thr, 0, 0, cat)
        got, nl, _ = pk.split_stream(
            jnp.array(P), start, cnt, feat // per, (feat % per) * lay.bits, 0, 0, thr, cat,
            num_features=lay.F, num_bins=32, bits=lay.bits, rows=lay.rows, interpret=INTERP)
        assert int(nl) == nl_want
        np.testing.assert_array_equal(np.asarray(got), want)

    def test_the_tiles_it_multiplies(self):
        """One triangle and a two-tile window a source tile and side, where
        the dense form took 64 + 128."""
        assert pk.perm_tiles() == 33 and pk._TILES == 8
        shapes = [tuple(v.shape) for v in pk._compaction_scratch(512)]
        assert shapes == [(128, 128), (16, 256, 128), (2, 128, pk.BLK + 128)]
        assert not any(sum(d >= pk.BLK for d in sh) > 1 for sh in shapes)  # nothing (BLK, BLK)


class TestTwoEndProtocol:
    """Host-side block-level simulation of split_stream's two-ended
    read/write protocol (demand reads, force-consume, hand-side prefetch,
    flush-waits) — proves writes only ever land on consumed blocks."""

    BLK = pk.BLK
    RING = pk._RING

    def _run(self, nblk, seed, bias):
        rng = random.Random(seed)
        BLK, RING = self.BLK, self.RING
        head = rng.randrange(0, BLK)
        total = nblk * BLK
        E = total - rng.randrange(0, BLK)
        cnt = E - head
        if cnt <= 0:
            return
        cl, cr = head, total - E
        if_ = ib = cf = cb = kf = kb = fl = fr = 0
        classified = set()

        def flushwait(tgt):
            nonlocal cf, cb
            if if_ > cf and tgt == cf:
                cf += 1
            if ib > cb and tgt == nblk - 1 - cb:
                cb += 1
            assert (tgt < cf) or (tgt >= nblk - cb), "flush to unread block"
            if if_ > cf:
                assert tgt != cf, "flush over in-flight front read"
            if ib > cb:
                assert tgt != nblk - 1 - cb, "flush over in-flight back read"

        for j in range(nblk):
            budget = if_ + ib < nblk
            if (cf - fl == 0) and ((if_ > cf) or budget):
                if if_ == cf:
                    if_ += 1
                cf += 1
            budget = if_ + ib < nblk
            if (cb - fr == 0) and ((ib > cb) or budget):
                if ib == cb:
                    ib += 1
                cb += 1
            budget = if_ + ib < nblk
            if cf - kf == 0 and cb - kb == 0:
                if (if_ > cf) or budget:
                    if if_ == cf:
                        if_ += 1
                    cf += 1
                else:
                    assert (ib > cb) or budget, "deadlock"
                    if ib == cb:
                        ib += 1
                    cb += 1
            useF = (cf - kf) > 0
            if useF:
                hand = kf
                kf += 1
            else:
                assert cb - kb > 0, "no hand block"
                hand = nblk - 1 - kb
                kb += 1
            assert hand not in classified, "block classified twice"
            classified.add(hand)
            lo, hi = hand * BLK, (hand + 1) * BLK
            nvalid = max(0, min(hi, E) - max(lo, head))
            r = rng.random()
            dl = 0 if r < bias else (nvalid if r < 2 * bias else rng.randint(0, nvalid))
            dr = nvalid - dl
            tl, tr = cl + dl, cr + dr
            if tl >= BLK:
                flushwait(fl)
                fl += 1
                tl -= BLK
            if tr >= BLK:
                flushwait(nblk - 1 - fr)
                fr += 1
                tr -= BLK
            cl, cr = tl, tr
            budget = if_ + ib < nblk
            if budget and useF and (if_ - kf) < RING:
                if_ += 1
            budget = if_ + ib < nblk
            if budget and (not useF) and (ib - kb) < RING:
                ib += 1

        assert cl + cr in (0, BLK)
        if cl + cr == BLK:
            flushwait(fl)
            assert fl == nblk - 1 - fr
        assert classified == set(range(nblk))
        assert if_ - cf <= 1 and ib - cb <= 1  # final drain bound

    def test_protocol(self):
        for bias in (0.05, 0.45):
            for nblk in list(range(1, 12)) + [50, 200]:
                for seed in range(300):
                    self._run(nblk, seed, bias)


class TestUpdateChannels:
    def test_grad_score_sel(self):
        n = 3000
        P, lay, bins, g, h, sel = _make_packed(n=n)
        rng = np.random.default_rng(5)
        delta = rng.standard_normal(n).astype(np.float32)
        sel_new = (rng.random(n) < 0.5).astype(np.float32)

        def grad_fn(score, label, weight):
            ps = 1.0 / (1.0 + jnp.exp(-score))
            return (ps - label) * weight, ps * (1.0 - ps) * weight

        P2 = update = pk.update_channels(P, lay, grad_fn, delta=delta, sel=sel_new,
                                         interpret=INTERP)
        P2n = np.asarray(P2)
        label = np.asarray(P, np.int32)[lay.LABEL, :n].view(np.float32)
        weight = np.asarray(P, np.int32)[lay.WEIGHT, :n].view(np.float32)
        score0 = np.asarray(P, np.int32)[lay.SCORE, :n].view(np.float32)
        s = score0 + delta
        ps = 1.0 / (1.0 + np.exp(-s))
        np.testing.assert_allclose(P2n[lay.SCORE, :n].view(np.float32), s, rtol=1e-6)
        np.testing.assert_allclose(
            P2n[lay.G, :n].view(np.float32), (ps - label) * weight, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(
            P2n[lay.H, :n].view(np.float32), ps * (1 - ps) * weight, rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(P2n[lay.SEL, :n].view(np.float32), sel_new)
        # immutable rows untouched
        np.testing.assert_array_equal(P2n[: lay.W], np.asarray(P)[: lay.W])
        np.testing.assert_array_equal(P2n[lay.ROWID], np.asarray(P)[lay.ROWID])


def _tiled_case(L, n, live, seed, values=None, cuts=None):
    """A ``segment_values`` case whose ``live`` non-empty segments tile
    ``[0, n)`` in shuffled slots of an ``L``-slot table; the slots past
    ``num_splits`` hold stale starts and counts, as a finished tree's do.
    Returns (starts, cnts, num_splits, values, n, expected row vector)."""
    rng = np.random.default_rng(seed)
    if cuts is None:
        cuts = np.sort(rng.choice(np.arange(1, n), size=live - 1, replace=False))
    seg_start = np.concatenate([[0], cuts]).astype(np.int64)
    seg_cnt = np.diff(np.concatenate([seg_start, [n]]))
    slots = rng.permutation(live)
    starts = rng.integers(0, n, size=L)  # stale
    cnts = rng.integers(1, n, size=L)
    starts[slots], cnts[slots] = seg_start, seg_cnt
    if values is None:
        values = rng.standard_normal(L)
    values = np.asarray(values, np.float32)
    expect = np.repeat(values[slots], seg_cnt)
    return starts, cnts, live - 1, values, n, expect


def _with_empty_neighbours():
    """Live slots of count 0 that share their start with the segment after
    them (a split that sent every row one way), one of them at 0 and one at
    ``n``."""
    starts = np.array([0, 0, 700, 700, 700, 1500, 2000, 11, 12], np.int64)
    cnts = np.array([0, 700, 0, 0, 800, 500, 0, 5, 6], np.int64)
    vals = np.arange(1, 10, dtype=np.float32)  # slots 7, 8 are inactive
    expect = np.repeat(vals[[1, 4, 5]], [700, 800, 500])
    return starts, cnts, 6, vals, 2000, expect


def _uncovered():
    """What the serial programs never hold and the sharded one does: rows no
    segment covers.  Before the first start they read the first segment's
    value, past the last segment's end the last one's (a shard's padded
    rows)."""
    starts, cnts = np.array([40, 10, 3000], np.int64), np.array([60, 30, 1], np.int64)
    vals = np.array([2.0, -3.0, 9.0], np.float32)
    expect = np.repeat(vals[[1, 0]], [40, 1160])
    return starts, cnts, 1, vals, 1200, expect


_SPECIAL = [-0.0, 0.0, 100.0, -100.0, 1e-38, -1e-38, 3.4e38, 0.1]
_SEGMENT_CASES = {
    "L4_the_old_case": lambda: _tiled_case(4, 20, 4, 0, [1.0, 2.0, 3.0, 4.0], cuts=[4, 10, 17]),
    "L31_n_off_1024": lambda: _tiled_case(31, 5000, 31, 1),
    "L255_full": lambda: _tiled_case(255, 3 * 1024 + 17, 255, 2),
    "L255_half_grown_stale_slots": lambda: _tiled_case(255, 4096, 97, 3),
    "no_split_one_segment": lambda: _tiled_case(31, 2500, 1, 4),
    "bounds_on_and_off_1024": lambda: _tiled_case(
        8, 8192, 8, 5, cuts=[1024, 2047, 2048, 2049, 4096, 7168, 8191]),
    "one_row_segments": lambda: _tiled_case(6, 3000, 6, 6, cuts=[1, 2, 1024, 1025, 2999]),
    "signed_zeros_and_clamps": lambda: _tiled_case(8, 2100, 8, 7, _SPECIAL),
    "keep_0_negative_zero_everywhere": lambda: _tiled_case(31, 1500, 31, 8, [-0.0] * 31),
    "empty_segments_share_a_start": _with_empty_neighbours,
    "uncovered_rows": _uncovered,
}


def _plain_meta_hyper(f, b):
    """(FeatureMeta, SplitHyper) of ``f`` numerical columns of ``b`` bins."""
    from lightgbm_tpu.ops.split import FeatureMeta, SplitHyper

    meta = FeatureMeta(
        num_bins=jnp.full((f,), b, jnp.int32),
        default_bin=jnp.zeros((f,), jnp.int32),
        is_categorical=jnp.zeros((f,), bool),
    )
    hyper = SplitHyper(
        lambda_l1=jnp.float32(0.0), lambda_l2=jnp.float32(0.01),
        min_data_in_leaf=jnp.float32(20), min_sum_hessian_in_leaf=jnp.float32(1e-3),
        min_gain_to_split=jnp.float32(0.0),
    )
    return meta, hyper


class TestGrowParity:
    def test_tree_matches_mask_grower(self):
        """grow_tree_partitioned must reproduce grow_tree's split records
        on identical inputs (same histogram math to f32 tolerance; any
        divergence means a partition/histogram bug)."""
        from lightgbm_tpu.ops.grow import GrowParams, grow_tree

        n, f, b, L = 6000, 11, 32, 15
        P, lay, bins, g, h, sel = _make_packed(n, f, b)
        meta, hyper = _plain_meta_hyper(f, b)
        fmask = jnp.ones((f,), jnp.float32)
        pres, P2 = grow_tree_partitioned(
            P, fmask, meta, hyper,
            PGrowParams(L, b, f, n, -1, True, False), interpret=INTERP,
        )
        gres = grow_tree(
            jnp.asarray(bins), jnp.asarray(g), jnp.asarray(h), jnp.asarray(sel),
            fmask, meta, hyper, GrowParams(num_leaves=L, num_bins=b),
        )
        ns = int(pres.num_splits)
        assert ns == int(gres.num_splits) and ns > 3
        np.testing.assert_array_equal(np.asarray(pres.rec_feat[:ns]), np.asarray(gres.rec_feat[:ns]))
        np.testing.assert_array_equal(np.asarray(pres.rec_thr[:ns]), np.asarray(gres.rec_thr[:ns]))
        np.testing.assert_array_equal(np.asarray(pres.rec_leaf[:ns]), np.asarray(gres.rec_leaf[:ns]))
        np.testing.assert_allclose(
            np.asarray(pres.rec_lval[:ns]), np.asarray(gres.rec_lval[:ns]), rtol=2e-4, atol=1e-6
        )
        # leaf assignment round-trips through the rowid channel
        lid = leaf_id_from_segments(pres, P2, lay, n)
        np.testing.assert_array_equal(np.asarray(lid), np.asarray(gres.leaf_id))

    @pytest.mark.parametrize("case", sorted(_SEGMENT_CASES))
    def test_segment_values(self, case):
        """Every row of a leaf gets the BITS of ``values[leaf]`` (-0.0 stays
        -0.0): compared as int32 with numpy's repeat over the live segments in
        start order."""
        import types

        starts, cnts, num_splits, vals, n, expect = _SEGMENT_CASES[case]()
        tree = types.SimpleNamespace(starts=jnp.asarray(starts, jnp.int32),
                                     cnts=jnp.asarray(cnts, jnp.int32),
                                     num_splits=jnp.int32(num_splits))
        out = np.asarray(segment_values(tree, n, jnp.asarray(vals, jnp.float32)))
        assert out.shape == (n,) and out.dtype == np.float32
        np.testing.assert_array_equal(out.view(np.int32),
                                      np.asarray(expect, np.float32).view(np.int32))


class TestFourBitPacking:
    """max_bin <= 16 -> 4-bit packed words (dense_nbits_bin.hpp:37):
    half the bin rows, identical results."""

    def test_kernel_parity_bits4(self):
        rng = np.random.default_rng(11)
        n, f, b = 5000, 11, 16
        lay = pk.PLayout(f, bits=4)
        assert lay.W == -(-f // 8)  # half the 8-bit word count
        bins = rng.integers(0, b, size=(n, f), dtype=np.uint8)
        P = pk.pack_matrix(bins, lay, label=rng.random(n).astype(np.float32))
        g = rng.standard_normal(n).astype(np.float32)
        h = np.abs(rng.standard_normal(n)).astype(np.float32)
        P = P.at[lay.G, :n].set(jnp.asarray(g.view(np.int32)))
        P = P.at[lay.H, :n].set(jnp.asarray(h.view(np.int32)))
        hd = np.asarray(pk.hist_dyn(P, 123, 3000, f, b, bits=4, rows=lay.rows,
                                    interpret=INTERP))
        hr = np.asarray(pk.hist_ref(P, 123, 3000, lay, b))
        err = np.abs(hd - hr).max() / max(np.abs(hr).max(), 1.0)
        assert err < (2e-3 if INTERP else 1e-5)
        _check_split_stream(P, lay, 100, 2000, 5, 7, 0, 0, 0, bits=4, nbins=b)

    def test_training_parity_bits4(self, monkeypatch):
        import lightgbm_tpu as lgb

        rng = np.random.default_rng(12)
        X = rng.standard_normal((3000, 8)).astype(np.float32)
        w = rng.standard_normal(8)
        y = (rng.random(3000) < 1 / (1 + np.exp(-(X @ w)))).astype(np.float32)
        params = dict(objective="binary", num_leaves=15, learning_rate=0.2,
                      max_bin=15, min_data_in_leaf=20, verbose=-1,
                      enable_bundle=False)
        preds = {}
        monkeypatch.delenv("LIGHTGBM_TPU_FORCE_BITS", raising=False)
        for mode, env in [("pgrow4", "force"), ("default", "0")]:
            monkeypatch.setenv("LIGHTGBM_TPU_PGROW", env)
            bst = lgb.train(params, lgb.Dataset(X, label=y, params=dict(params)), 3)
            if mode == "pgrow4":
                assert bst.boosting.ptrainer.params.bits == 4
                assert bst.boosting.ptrainer.layout.W == 1  # 8 feats, 1 word
            preds[mode] = bst.predict(X)
        np.testing.assert_allclose(preds["pgrow4"], preds["default"], rtol=3e-3, atol=3e-4)


class TestFusedTrainer:
    def _data(self, n=3000, f=8, seed=0):
        rng = np.random.default_rng(seed)
        X = rng.standard_normal((n, f)).astype(np.float32)
        w = rng.standard_normal(f)
        y = (rng.random(n) < 1 / (1 + np.exp(-(X @ w)))).astype(np.float32)
        return X, y

    def test_matches_default_path(self, monkeypatch):
        import lightgbm_tpu as lgb

        X, y = self._data()
        params = dict(objective="binary", num_leaves=7, learning_rate=0.2,
                      max_bin=31, min_data_in_leaf=20, verbose=-1)
        preds = {}
        for mode, env in [("pgrow", "force"), ("default", "0")]:
            monkeypatch.setenv("LIGHTGBM_TPU_PGROW", env)
            bst = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=3)
            preds[mode] = bst.predict(X)
            if mode == "pgrow":
                assert bst.boosting.ptrainer is not None
            else:
                assert bst.boosting.ptrainer is None
        np.testing.assert_allclose(preds["pgrow"], preds["default"], rtol=3e-3, atol=3e-4)

    def test_regression_weighted(self, monkeypatch):
        import lightgbm_tpu as lgb

        rng = np.random.default_rng(1)
        X = rng.standard_normal((2000, 6)).astype(np.float32)
        y = (X[:, 0] - 0.5 * X[:, 1] + 0.1 * rng.standard_normal(2000)).astype(np.float32)
        w = rng.random(2000).astype(np.float32) + 0.5
        params = dict(objective="regression", num_leaves=7, learning_rate=0.2,
                      max_bin=31, min_data_in_leaf=20, verbose=-1)
        preds = {}
        for mode, env in [("pgrow", "force"), ("default", "0")]:
            monkeypatch.setenv("LIGHTGBM_TPU_PGROW", env)
            ds = lgb.Dataset(X, label=y, weight=w)
            bst = lgb.train(params, ds, num_boost_round=3)
            preds[mode] = bst.predict(X)
        np.testing.assert_allclose(preds["pgrow"], preds["default"], rtol=3e-3, atol=3e-4)

    def test_rank_objective_falls_back(self, monkeypatch):
        import lightgbm_tpu as lgb

        monkeypatch.setenv("LIGHTGBM_TPU_PGROW", "force")
        rng = np.random.default_rng(2)
        X = rng.standard_normal((600, 5)).astype(np.float32)
        y = rng.integers(0, 3, 600).astype(np.float32)
        ds = lgb.Dataset(X, label=y, group=[60] * 10)
        bst = lgb.train(
            dict(objective="lambdarank", num_leaves=7, max_bin=31, verbose=-1),
            ds, num_boost_round=2,
        )
        assert bst.boosting.ptrainer is None
        assert bst.boosting.num_trees >= 2


class TestUpdateAndRootHist:
    def test_fused_update_hist(self):
        n = 3000
        P, lay, bins, g, h, sel = _make_packed(n=n)
        rng = np.random.default_rng(9)
        delta = rng.standard_normal(n).astype(np.float32)
        sel_new = (rng.random(n) < 0.6).astype(np.float32)

        def grad_fn(score, label, weight):
            ps = 1.0 / (1.0 + jnp.exp(-score))
            return (ps - label) * weight, ps * (1.0 - ps) * weight

        P2, hist = pk.update_and_root_hist(
            P, lay, grad_fn, delta=delta, sel=sel_new, num_rows=n,
            num_features=lay.F, num_bins=32, interpret=INTERP)
        P2n = np.asarray(P2, np.int32)
        label = np.asarray(P, np.int32)[lay.LABEL, :n].view(np.float32)
        weight = np.asarray(P, np.int32)[lay.WEIGHT, :n].view(np.float32)
        s = np.asarray(P, np.int32)[lay.SCORE, :n].view(np.float32) + delta
        ps = 1.0 / (1.0 + np.exp(-s))
        np.testing.assert_allclose(P2n[lay.SCORE, :n].view(np.float32), s, rtol=1e-6)
        np.testing.assert_allclose(
            P2n[lay.G, :n].view(np.float32), (ps - label) * weight, rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(P2n[lay.SEL, :n].view(np.float32), sel_new)
        np.testing.assert_array_equal(P2n[: lay.W], np.asarray(P)[: lay.W])
        # returned hist matches hist_ref on the UPDATED matrix
        hr = np.asarray(pk.hist_ref(P2, 0, n, lay, 32))
        err = np.abs(np.asarray(hist) - hr).max() / max(np.abs(hr).max(), 1.0)
        assert err < (2e-3 if INTERP else 1e-5)


class TestShardedPartitioned:
    """Data-parallel partitioned trainer (shard_map + hist psum) must
    reproduce the serial partitioned trainer tree-for-tree."""

    def test_dp_matches_serial(self, monkeypatch):
        import lightgbm_tpu as lgb

        if len(jax.devices()) < 4:
            pytest.skip("needs multi-device mesh")
        rng = np.random.default_rng(3)
        X = rng.standard_normal((3000, 8)).astype(np.float32)
        w = rng.standard_normal(8)
        y = (rng.random(3000) < 1 / (1 + np.exp(-(X @ w)))).astype(np.float32)
        params = dict(objective="binary", num_leaves=15, learning_rate=0.2,
                      max_bin=31, min_data_in_leaf=20, verbose=-1)
        monkeypatch.setenv("LIGHTGBM_TPU_PGROW", "force")
        preds, models = {}, {}
        for mode in ("serial", "data"):
            p = dict(params, tree_learner=mode)
            bst = lgb.train(p, lgb.Dataset(X, label=y, params=dict(p)), 3)
            if mode == "data":
                from lightgbm_tpu.boosting.ptrainer import ShardedPartitionedTrainer
                assert isinstance(bst.boosting.ptrainer, ShardedPartitionedTrainer)
            preds[mode] = bst.predict(X)
            models[mode] = bst.boosting.save_model_to_string()
        # identical split structure (same hist sums to f32 tolerance)
        np.testing.assert_allclose(preds["data"], preds["serial"], rtol=3e-3, atol=3e-4)

    def test_dp_multiclass_matches_serial(self, monkeypatch):
        """K > 1 under the sharded trainer: K score channels in the
        sharded layout, one multi-hist psum per iteration."""
        import lightgbm_tpu as lgb

        if len(jax.devices()) < 4:
            pytest.skip("needs multi-device mesh")
        rng = np.random.default_rng(13)
        n, f, K = 2400, 6, 3
        X = rng.standard_normal((n, f)).astype(np.float32)
        w = rng.standard_normal((f, K))
        y = np.argmax(X @ w + 0.3 * rng.standard_normal((n, K)), axis=1).astype(np.float32)
        params = dict(objective="multiclass", num_class=K, num_leaves=7,
                      learning_rate=0.2, max_bin=31, min_data_in_leaf=20,
                      verbose=-1)
        monkeypatch.setenv("LIGHTGBM_TPU_PGROW", "force")
        preds = {}
        for mode in ("serial", "data"):
            p = dict(params, tree_learner=mode)
            bst = lgb.train(p, lgb.Dataset(X, label=y, params=dict(p)), 3)
            if mode == "data":
                from lightgbm_tpu.boosting.ptrainer import ShardedPartitionedTrainer
                assert isinstance(bst.boosting.ptrainer, ShardedPartitionedTrainer)
                assert bst.boosting.ptrainer.K == K
            preds[mode] = bst.predict(X)
        np.testing.assert_allclose(preds["data"], preds["serial"], rtol=4e-3, atol=5e-4)

    def test_dp_goss_trains(self, monkeypatch):
        """GOSS under the sharded trainer: per-shard local top-k (the
        reference's distributed GOSS is also per-machine local).  Sampling
        draws differ from serial by design, so assert training quality
        rather than tree equality."""
        import lightgbm_tpu as lgb

        if len(jax.devices()) < 4:
            pytest.skip("needs multi-device mesh")
        rng = np.random.default_rng(14)
        n, f = 3000, 8
        X = rng.standard_normal((n, f)).astype(np.float32)
        w = rng.standard_normal(f)
        y = (rng.random(n) < 1 / (1 + np.exp(-(X @ w)))).astype(np.float32)
        params = dict(objective="binary", boosting="goss", num_leaves=15,
                      learning_rate=0.5, max_bin=31, min_data_in_leaf=20,
                      tree_learner="data", verbose=-1)
        monkeypatch.setenv("LIGHTGBM_TPU_PGROW", "force")
        bst = lgb.train(params, lgb.Dataset(X, label=y, params=dict(params)), 6)
        from lightgbm_tpu.boosting.ptrainer import ShardedPartitionedTrainer
        assert isinstance(bst.boosting.ptrainer, ShardedPartitionedTrainer)
        from sklearn.metrics import roc_auc_score
        auc = roc_auc_score(y, bst.predict(X))
        # Until PR 30 the fused trainers read `config.boosting`, which does not
        # exist (the parameter is `boosting_type`), and trained plain GBDT here:
        # AUC 0.872, over the 0.85 this test asked.  GOSS proper (4 of the 6
        # trees from 30% of the rows) reads 0.833-0.845 on this table on the
        # mask grower, the serial and the sharded fused trainer alike, so the
        # quality is held against the serial fused trainer's.
        serial = dict(params, tree_learner="serial")
        ref = lgb.train(serial, lgb.Dataset(X, label=y, params=dict(serial)), 6)
        ref_auc = roc_auc_score(y, ref.predict(X))
        assert auc > 0.8 and abs(auc - ref_auc) < 0.03, (auc, ref_auc)
        # and GOSS really sampled: the last iteration selected under half the rows
        pt, lay = bst.boosting.ptrainer, bst.boosting.ptrainer.layout
        sel = np.asarray(pt.p)[:, lay.SEL, :pt.num_rows].view(np.float32)
        assert 0.2 * n < sel.sum() < 0.5 * n


class TestFusedRollback:
    """rollback_one_iter against the fused trainers: the popped tree's
    contribution must leave the score channel exactly (r5 ADVICE fixes:
    last_kept tracking + post-stop no-op iterations keep the physical
    layout the positional rollback needs)."""

    def _problem(self, n=2000, f=6, seed=21):
        rng = np.random.default_rng(seed)
        X = rng.standard_normal((n, f)).astype(np.float32)
        w = rng.standard_normal(f)
        y = (rng.random(n) < 1 / (1 + np.exp(-(X @ w)))).astype(np.float32)
        return X, y

    def test_rollback_matches_shorter_run(self, monkeypatch):
        import lightgbm_tpu as lgb

        monkeypatch.setenv("LIGHTGBM_TPU_PGROW", "force")
        X, y = self._problem()
        params = dict(objective="binary", num_leaves=15, learning_rate=0.2,
                      max_bin=31, min_data_in_leaf=20, verbose=-1)
        bst3 = lgb.train(params, lgb.Dataset(X, label=y, params=dict(params)), 3)
        bst3.rollback_one_iter()
        assert bst3.num_trees == 2
        bst2 = lgb.train(params, lgb.Dataset(X, label=y, params=dict(params)), 2)
        np.testing.assert_allclose(bst3.predict(X), bst2.predict(X),
                                   rtol=1e-5, atol=1e-6)
        # the internal score channel must match the 2-tree state too:
        # training ONE more iteration reproduces the deterministic tree 3
        bst3.update()
        ref3 = lgb.train(params, lgb.Dataset(X, label=y, params=dict(params)), 3)
        np.testing.assert_allclose(bst3.predict(X), ref3.predict(X),
                                   rtol=3e-4, atol=3e-5)

    def test_sharded_bagging_uneven_shards(self, monkeypatch):
        """Bagging + rows that don't divide across shards: before the r5
        validity fix, split_stream's permutation let PADDING rows enter
        histograms on later iterations (positional mask), corrupting
        training.  2003 rows over 8 shards leaves 5 shards padded."""
        import jax as _jax
        import lightgbm_tpu as lgb

        if len(_jax.devices()) < 4:
            pytest.skip("needs multi-device mesh")
        monkeypatch.setenv("LIGHTGBM_TPU_PGROW", "force")
        X, y = self._problem(n=2003)
        params = dict(objective="binary", num_leaves=15, learning_rate=0.2,
                      max_bin=31, min_data_in_leaf=20, tree_learner="data",
                      bagging_fraction=0.7, bagging_freq=1, verbose=-1)
        bst = lgb.train(params, lgb.Dataset(X, label=y, params=dict(params)), 6)
        from lightgbm_tpu.boosting.ptrainer import ShardedPartitionedTrainer

        assert isinstance(bst.boosting.ptrainer, ShardedPartitionedTrainer)
        from sklearn.metrics import roc_auc_score

        auc = roc_auc_score(y, bst.predict(X))
        assert auc > 0.85, auc


class TestMulticlassFused:
    def test_multiclass_matches_default(self, monkeypatch):
        import lightgbm_tpu as lgb

        rng = np.random.default_rng(7)
        n, f, K = 2400, 6, 3
        X = rng.standard_normal((n, f)).astype(np.float32)
        w = rng.standard_normal((f, K))
        y = np.argmax(X @ w + 0.3 * rng.standard_normal((n, K)), axis=1).astype(np.float32)
        params = dict(objective="multiclass", num_class=K, num_leaves=7,
                      learning_rate=0.2, max_bin=31, min_data_in_leaf=20,
                      verbose=-1)
        preds = {}
        for mode, env in [("pgrow", "force"), ("default", "0")]:
            monkeypatch.setenv("LIGHTGBM_TPU_PGROW", env)
            bst = lgb.train(params, lgb.Dataset(X, label=y, params=dict(params)), 3)
            if mode == "pgrow":
                assert bst.boosting.ptrainer is not None
                assert bst.boosting.ptrainer.K == K
            preds[mode] = bst.predict(X)
        np.testing.assert_allclose(preds["pgrow"], preds["default"], rtol=4e-3, atol=5e-4)

    def test_multiclassova_matches_default(self, monkeypatch):
        import lightgbm_tpu as lgb

        rng = np.random.default_rng(8)
        n, f, K = 1800, 5, 3
        X = rng.standard_normal((n, f)).astype(np.float32)
        w = rng.standard_normal((f, K))
        y = np.argmax(X @ w, axis=1).astype(np.float32)
        params = dict(objective="multiclassova", num_class=K, num_leaves=7,
                      learning_rate=0.2, max_bin=31, min_data_in_leaf=20,
                      verbose=-1)
        preds = {}
        for mode, env in [("pgrow", "force"), ("default", "0")]:
            monkeypatch.setenv("LIGHTGBM_TPU_PGROW", env)
            bst = lgb.train(params, lgb.Dataset(X, label=y, params=dict(params)), 3)
            preds[mode] = bst.predict(X)
        np.testing.assert_allclose(preds["pgrow"], preds["default"], rtol=4e-3, atol=5e-4)


class TestGossFused:
    def test_goss_matches_mask_path(self, monkeypatch):
        import lightgbm_tpu as lgb

        rng = np.random.default_rng(4)
        n, f = 3000, 8
        X = rng.standard_normal((n, f)).astype(np.float32)
        w = rng.standard_normal(f)
        y = (rng.random(n) < 1 / (1 + np.exp(-(X @ w)))).astype(np.float32)
        # learning_rate 0.5 -> GOSS sampling kicks in from iteration 2
        params = dict(objective="binary", boosting="goss", num_leaves=15,
                      learning_rate=0.5, max_bin=31, min_data_in_leaf=20,
                      top_rate=0.3, other_rate=0.2, verbose=-1)
        aucs = {}
        for mode, env in [("pgrow", "force"), ("default", "0")]:
            monkeypatch.setenv("LIGHTGBM_TPU_PGROW", env)
            bst = lgb.train(params, lgb.Dataset(X, label=y, params=dict(params)), 6)
            if mode == "pgrow":
                assert bst.boosting.ptrainer is not None
            pred = bst.predict(X)
            # RNG streams differ (threefry key vs split) -> compare
            # quality, not per-row predictions
            from sklearn.metrics import roc_auc_score
            aucs[mode] = roc_auc_score(y, pred)
        assert aucs["pgrow"] > 0.8 and aucs["default"] > 0.8
        assert abs(aucs["pgrow"] - aucs["default"]) < 0.05

    def test_goss_warm_iters_identical(self, monkeypatch):
        """Before 1/learning_rate iterations GOSS does no sampling, so
        fused and mask paths must agree exactly (to f32 tolerance)."""
        import lightgbm_tpu as lgb

        rng = np.random.default_rng(5)
        n, f = 2500, 6
        X = rng.standard_normal((n, f)).astype(np.float32)
        y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float32)
        params = dict(objective="binary", boosting="goss", num_leaves=7,
                      learning_rate=0.1, max_bin=31, min_data_in_leaf=20,
                      verbose=-1)  # warm window = 10 iters > 3 trained
        preds = {}
        for mode, env in [("pgrow", "force"), ("default", "0")]:
            monkeypatch.setenv("LIGHTGBM_TPU_PGROW", env)
            bst = lgb.train(params, lgb.Dataset(X, label=y, params=dict(params)), 3)
            preds[mode] = bst.predict(X)
        np.testing.assert_allclose(preds["pgrow"], preds["default"], rtol=3e-3, atol=3e-4)


class TestLevelGrowerCaps:
    """Stress the level grower where its static caps bind (VERDICT item
    7): num_leaves=1023 exceeds the 512-slot frontier and the level
    budget it implies, and the level-batched path must stay
    tree-identical to the per-split grower."""

    def test_num_leaves_1023_parity_with_levelgrow_off(self, monkeypatch):
        import lightgbm_tpu as lgb

        rng = np.random.default_rng(3)
        n, f = 5000, 8
        X = rng.standard_normal((n, f)).astype(np.float32)
        w = rng.standard_normal(f)
        y = (rng.random(n) < 1 / (1 + np.exp(-(X @ w)))).astype(np.float32)
        params = dict(objective="binary", num_leaves=1023, learning_rate=0.2,
                      max_bin=31, min_data_in_leaf=1, verbose=-1)
        monkeypatch.setenv("LIGHTGBM_TPU_PGROW", "force")
        preds = {}
        leaves = {}
        for mode in ("1", "0"):
            monkeypatch.setenv("LIGHTGBM_TPU_LEVELGROW", mode)
            bst = lgb.train(params, lgb.Dataset(X, label=y, params=dict(params)), 2)
            assert bst.boosting.ptrainer is not None
            assert bst.boosting.ptrainer.params.levelwise == (mode == "1")
            preds[mode] = bst.predict(X)
            leaves[mode] = [t.num_leaves for t in bst.boosting.models]
        # with min_data_in_leaf=1 and 5000 rows the 1023-leaf cap BINDS
        assert leaves["1"] == leaves["0"]
        assert max(leaves["1"]) == 1023, leaves
        # level-batched growth is tree-identical to per-split growth
        np.testing.assert_array_equal(preds["1"], preds["0"])

    @pytest.mark.parametrize("num_leaves,rows,min_hess,binds", [
        (31, 6000, 1e-3, "table"), (63, 3000, 4.0, "levels")])
    def test_level_phase_is_bounded_by_its_table(self, monkeypatch, num_leaves, rows,
                                                 min_hess, binds):
        """The level phase runs at most log2(SMAX) + 1 levels and none on a
        full candidate table (each one streams all SMAX slots' histograms
        whatever it holds); what it leaves is the replay's tail, and the trees
        are those of the per-split grower."""
        import lightgbm_tpu as lgb

        rng = np.random.default_rng(5)
        X = rng.standard_normal((rows, 6)).astype(np.float32)
        w = rng.standard_normal(6)
        y = (rng.random(rows) < 1 / (1 + np.exp(-(X @ w)))).astype(np.float32)
        params = dict(objective="binary", num_leaves=num_leaves, learning_rate=0.3,
                      max_bin=31, min_data_in_leaf=1, min_sum_hessian_in_leaf=min_hess,
                      verbose=-1)
        monkeypatch.setenv("LIGHTGBM_TPU_PGROW", "force")
        counts, preds, leaves = [], {}, {}
        for mode in ("1", "0"):
            monkeypatch.setenv("LIGHTGBM_TPU_LEVELGROW", mode)
            bst = lgb.Booster(params=params,
                              train_set=lgb.Dataset(X, label=y, params=dict(params)))
            pt = bst.boosting.ptrainer
            chunk = pt.train_chunk

            def keep(*a, _chunk=chunk, **k):
                out = _chunk(*a, **k)
                counts.extend(out[0]["levels"][:, 0].tolist())
                return out

            pt.train_chunk = keep
            bst.boosting.train_iters_partitioned(6, is_eval=False)
            preds[mode] = bst.predict(X)
            leaves[mode] = [t.num_leaves for t in bst.boosting.models]
        np.testing.assert_array_equal(preds["1"], preds["0"])
        assert leaves["1"] == leaves["0"] and max(leaves["1"]) == num_leaves
        levels, _, segments, scanned = np.array(counts[:6]).T  # LEVELGROW=0 counts none
        assert not np.array(counts[6:]).any()
        smax = -(-(num_leaves + 1) // 8) * 8
        assert (scanned == levels * smax).all()  # 6 columns: the search has no loop
        cap = (smax - 1).bit_length() + 1
        assert levels.max() <= cap and segments.max() <= smax - 1, counts
        if binds == "table":
            # balanced trees fill the 2 * SMAX slots in the doubling levels:
            # the phase ends there, with no level that holds no segment
            assert (levels == cap - 1).all() and (segments == smax - 1).all(), counts
        else:
            # unbalanced trees leave slots free after the last level: the
            # tail takes those splits, and the trees above are still full
            assert (levels == cap).all() and segments.min() < smax - 1, counts


class TestScoreAddBand:
    """score_add streams ONLY the 8-aligned mutable band (PR-6 fused
    score-update): exact += on the target score row, every other row —
    including the packed bin words it no longer reads — bit-identical."""

    def test_band_add_exact(self):
        n = 3000
        P, lay, bins, g, h, sel = _make_packed(n=n)
        rng = np.random.default_rng(21)
        delta = rng.standard_normal(n).astype(np.float32)
        P0 = np.asarray(P, np.int32)
        P2 = pk.score_add(jnp.array(P), lay, jnp.asarray(delta), 0,
                          num_rows=n, interpret=INTERP)
        P2n = np.asarray(P2, np.int32)
        want = P0[lay.SCORE, :n].view(np.float32) + delta
        np.testing.assert_array_equal(
            P2n[lay.SCORE, :n].view(np.float32), want)
        # nothing else moved (bin words, g/h, sel, label, rowid, weight)
        other = [r for r in range(lay.C) if r != lay.SCORE]
        np.testing.assert_array_equal(P2n[other][:, :n], P0[other][:, :n])

    def test_multiclass_channel_k(self):
        n = 2000
        rng = np.random.default_rng(22)
        f, K = 6, 3
        lay = pk.PLayout(f, num_score=K)
        bins = rng.integers(0, 16, size=(n, f), dtype=np.uint8)
        P = pk.pack_matrix(bins, lay, label=rng.random(n).astype(np.float32))
        delta = rng.standard_normal(n).astype(np.float32)
        P0 = np.asarray(P, np.int32)
        P2 = pk.score_add(jnp.array(P), lay, jnp.asarray(delta), 1,
                          num_rows=n, interpret=INTERP)
        P2n = np.asarray(P2, np.int32)
        np.testing.assert_array_equal(
            P2n[lay.SCORE + 1, :n].view(np.float32),
            P0[lay.SCORE + 1, :n].view(np.float32) + delta)
        other = [r for r in range(lay.C) if r != lay.SCORE + 1]
        np.testing.assert_array_equal(P2n[other][:, :n], P0[other][:, :n])


class TestUpdateHistFree:
    """update_and_root_hist(with_hist=False) — the GOSS gradient-prep /
    settle fast path — must write the exact same matrix as the
    histogram-carrying pass, just without the discarded histogram."""

    def test_matrix_bit_identical(self):
        n = 3000
        P, lay, bins, g, h, sel = _make_packed(n=n)
        rng = np.random.default_rng(23)
        delta = rng.standard_normal(n).astype(np.float32)
        sel_new = (rng.random(n) < 0.6).astype(np.float32)

        def grad_fn(score, label, weight):
            ps = 1.0 / (1.0 + jnp.exp(-score))
            return (ps - label) * weight, ps * (1.0 - ps) * weight

        Pa, hist = pk.update_and_root_hist(
            jnp.array(P), lay, grad_fn, delta=delta, sel=sel_new, num_rows=n,
            num_features=lay.F, num_bins=32, interpret=INTERP)
        Pb, no_hist = pk.update_and_root_hist(
            jnp.array(P), lay, grad_fn, delta=delta, sel=sel_new, num_rows=n,
            num_features=lay.F, num_bins=32, with_hist=False, interpret=INTERP)
        assert no_hist is None
        assert hist is not None and np.asarray(hist).shape == (lay.F, 32, 3)
        np.testing.assert_array_equal(np.asarray(Pa, np.int32),
                                      np.asarray(Pb, np.int32))


class TestEmptySegmentLaunch:
    """split_stream on the empty segment at 0 is what the replay launches
    when a split's children were precomputed by the level phase (PR 27:
    the kernel runs outside the ``has_pre`` conditional so that the
    packed matrix never passes through one).  It must be a no-op."""

    @pytest.mark.parametrize("bits,nbins", [(8, 32), (4, 16)])
    def test_start0_cnt0_touches_nothing(self, bits, nbins):
        rng = np.random.default_rng(31)
        n, f = 5000, 11
        lay = pk.PLayout(f, bits=bits)
        bins = rng.integers(0, nbins, size=(n, f), dtype=np.uint8)
        P = pk.pack_matrix(bins, lay, label=rng.random(n).astype(np.float32))
        for row in (lay.G, lay.H, lay.SEL):
            P = P.at[row, :n].set(jnp.asarray(
                np.abs(rng.standard_normal(n)).astype(np.float32).view(np.int32)))
        before = np.asarray(P, np.int32)
        per = 32 // bits
        P2, nl, planes = pk.split_stream(
            jnp.array(P), 0, 0, 5 // per, (5 % per) * bits, 0, 0, 7, 0,
            num_features=f, num_bins=nbins, bits=bits, rows=lay.rows,
            interpret=INTERP)
        assert int(nl) == 0
        np.testing.assert_array_equal(np.asarray(P2, np.int32), before)
        assert np.asarray(planes).shape == (6, pk.hist_lanes(f, nbins))
        assert not np.asarray(planes).any()


class TestChunkStops:
    """A chunk in which a tree comes out empty.  Since PR 27 the chunk
    program LEAVES its loop there (``~stopped`` is part of the loop's
    predicate; before, every later iteration took a no-op branch of a
    conditional that carried the whole matrix).  Either way the state
    after the chunk is the state the stopping iteration left."""

    LR = 1.0

    def _booster(self, kind, monkeypatch):
        import lightgbm_tpu as lgb

        monkeypatch.setenv("LIGHTGBM_TPU_PGROW", "force")
        rng = np.random.default_rng(5)
        n = 3000
        X = rng.standard_normal((n, 4)).astype(np.float32)
        X[:, 0] = rng.integers(0, 3, n)
        if kind == "const":
            y = np.full(n, 3.0, np.float32)  # no gradient: tree 0 is empty
        else:
            # three plateaus under stumps: the gains decay 4x a tree (about
            # 5900, 1470, 360, ...), and min_gain_to_split cuts the third
            y = np.asarray([0.0, 2.0, 5.0], np.float32)[X[:, 0].astype(int)]
        params = dict(objective="regression", num_leaves=2, learning_rate=self.LR,
                      max_bin=31, min_data_in_leaf=20, min_gain_to_split=1000.0,
                      verbose=-1)
        return lgb.Booster(params=params,
                           train_set=lgb.Dataset(X, label=y, params=dict(params)))

    @pytest.mark.parametrize("kind,stop_at", [("const", 0), ("step", 2)])
    def test_state_after_the_stop(self, monkeypatch, kind, stop_at):
        b = self._booster(kind, monkeypatch).boosting
        pt = b.ptrainer
        b._boost_from_average()
        if pt.score_dirty:
            pt.sync_scores_from(b.scores[0])
        prog = pt._build_program(4, False, 1, pt.params.num_features)
        p0 = np.asarray(pt.p)
        args = (jnp.float32(self.LR), pt._base_key, jnp.int32(0))
        whole = jax.device_get(prog(jnp.array(p0), *args, jnp.int32(4)))
        # the same program told to run only up to the stopping iteration
        short = jax.device_get(prog(jnp.array(p0), *args, jnp.int32(stop_at + 1)))
        ns = whole[1]["num_splits"][:, 0]
        assert (ns[:stop_at] > 0).all() and not ns[stop_at:].any()
        assert not whole[1]["raw"][stop_at:].any()
        for got, want in zip(jax.tree_util.tree_leaves(whole),
                             jax.tree_util.tree_leaves(short)):
            np.testing.assert_array_equal(got, want)
        if stop_at == 0:
            # nothing was kept: scores and rollback snapshot are untouched
            lay = pt.layout
            np.testing.assert_array_equal(whole[0][lay.SCORE], p0[lay.SCORE])
            assert not whole[3].any()

    def test_rollback_after_a_stopped_chunk(self, monkeypatch):
        bst = self._booster("step", monkeypatch)
        b = bst.boosting
        assert b.train_iters_partitioned(4, is_eval=False) is True
        assert b.iter == 2
        bst.rollback_one_iter()
        assert b.iter == 1 and not b.ptrainer.score_dirty
        ref = self._booster("step", monkeypatch).boosting
        assert ref.train_iters_partitioned(1, is_eval=False) is False
        np.testing.assert_allclose(
            np.asarray(b.ptrainer.scores_original_order()),
            np.asarray(ref.ptrainer.scores_original_order()), rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(b.scores), np.asarray(ref.scores),
                                   rtol=1e-5, atol=1e-6)

    def test_nothing_to_roll_back_when_the_first_tree_is_empty(self, monkeypatch):
        b = self._booster("const", monkeypatch).boosting
        assert b.train_iters_partitioned(4, is_eval=False) is True
        assert b.iter == 0 and b.ptrainer._last_tree is None
        np.testing.assert_array_equal(
            np.asarray(b.ptrainer.scores_original_order()), np.float32(3.0))


GOLDEN_RECS = os.path.join(os.path.dirname(__file__), "golden", "pgrow_recs.npz")


def _captured_chunk(X, y, params, sharded: bool, iterations: int, env=None):
    """(records, trainer) of one chunk of ``iterations`` iterations, as the
    chunk program returns them; four shards under ``sharded``.
    LIGHTGBM_TPU_PGROW=force must be set."""
    from unittest import mock

    import lightgbm_tpu as lgb
    import lightgbm_tpu.parallel as par

    params = dict(params, tree_learner="data" if sharded else "serial")
    mesh4 = par.make_mesh(4) if sharded else None
    with mock.patch.dict(os.environ, env or {}), \
            mock.patch.object(par, "make_mesh", lambda n_devices=None: mesh4):
        b = lgb.Booster(params=params,
                        train_set=lgb.Dataset(X, label=y, params=dict(params))).boosting
    pt = b.ptrainer
    assert getattr(pt, "d", 1) == (4 if sharded else 1)
    seen = {}
    run = pt.train_chunk

    def capture(*a, **k):
        out = run(*a, **k)
        seen["recs"] = out[0]
        return out

    with mock.patch.object(pt, "train_chunk", capture):
        b.train_iters_partitioned(iterations, is_eval=False)
    return seen["recs"], pt


def chunk_records(levelgrow: str, sharded: bool):
    """Split records of 3 iterations on a seeded 65,536-row table, as the
    chunk program returns them.  LIGHTGBM_TPU_PGROW=force must be set."""
    rng = np.random.default_rng(20270927)
    n, f = 65536, 10
    X = rng.standard_normal((n, f)).astype(np.float32)
    w = rng.standard_normal(f)
    y = (rng.random(n) < 1 / (1 + np.exp(-(X @ w + X[:, 0] * X[:, 1])))).astype(np.float32)
    params = dict(objective="binary", num_leaves=31, learning_rate=0.1, max_bin=63,
                  min_data_in_leaf=20, verbose=-1)
    recs, pt = _captured_chunk(X, y, params, sharded, 3,
                               env={"LIGHTGBM_TPU_LEVELGROW": levelgrow})
    assert pt.params.levelwise == (levelgrow == "1")
    assert recs["num_splits"].tolist() == [[30]] * 3
    return recs["raw"]


GOLDEN_CASES = [("serial_lg1", "1", False), ("serial_lg0", "0", False),
                ("dp4_lg1", "1", True), ("dp4_lg0", "0", True)]


class TestRecordsGolden:
    """tests/golden/pgrow_recs.npz was written by
    tests/golden/make_pgrow_recs.py from the PARENT of PR 27 (commit
    028fc30), whose change to the chunk program and the replay is
    control flow alone: of 90 replayed splits in the level-batched runs
    78 take precomputed children and 12 the ``split_stream`` tail, and
    not one byte of a record may differ.  A later PR that changes the
    arithmetic on purpose rewrites the file with that script and says so."""

    @pytest.mark.parametrize("name,levelgrow,sharded", GOLDEN_CASES)
    def test_records_byte_equal(self, monkeypatch, name, levelgrow, sharded):
        if sharded and len(jax.devices()) < 4:
            pytest.skip("needs 4 devices")
        monkeypatch.setenv("LIGHTGBM_TPU_PGROW", "force")
        want = np.load(GOLDEN_RECS)[name]
        got = chunk_records(levelgrow, sharded)
        assert got.dtype == want.dtype and got.shape == want.shape == (3, 1, 30, 12)
        assert got.tobytes() == want.tobytes()


def _wide_chunk(sharded: bool):
    """(records, trainer) of a chunk of 2 iterations, 63 leaves, on a seeded
    2,048 x 600 table.  LIGHTGBM_TPU_PGROW=force must be set."""
    rng = np.random.default_rng(20340600)
    n, f = 2048, 600
    X = rng.standard_normal((n, f)).astype(np.float32)
    w = rng.standard_normal(6)
    y = (rng.random(n) < 1 / (1 + np.exp(-(X[:, [0, 5, 299, 300, 598, 599]] @ w)))
         ).astype(np.float32)
    params = dict(objective="binary", num_leaves=63, learning_rate=0.1, max_bin=63,
                  min_data_in_leaf=1, min_sum_hessian_in_leaf=1e-3, verbose=-1)
    return _captured_chunk(X, y, params, sharded, 2)


class TestLevelSplitScan:
    """PR 34: the level phase's split search visits ``scan_batch`` slots at a
    time up to the level's active count, and a slot's search is the same
    ``find2`` on the same histogram whatever the batch: every field of every
    slot below ``n_act`` bit for bit."""

    SMAX, COLS, BINS = 32, 7, 32

    @pytest.fixture(scope="class")
    def level(self):
        from lightgbm_tpu.ops import pgrow

        s, f, b = self.SMAX, self.COLS, self.BINS
        rng = np.random.default_rng(20340034)
        lanes = pk.hist_lanes(f, b)
        rows = rng.standard_normal((s, 16, lanes)).astype(np.float32)
        rows[:, [3, 4, 5, 10, 11, 12]] = np.abs(rows[:, [3, 4, 5, 10, 11, 12]])  # hessians
        rows[:, [6, 13]] = rng.integers(0, 40, (s, 2, lanes))  # counts
        rows[:, [7, 14, 15]] = 0.0
        hists = jnp.asarray(rows)
        hist2 = jnp.stack([jax.vmap(lambda h, r=r: pk._hist_from_rows(h, f, b, row0=r))(hists)
                           for r in (0, 7)], axis=1)
        sums2 = jnp.sum(hist2[:, :, 0], axis=2)  # (SMAX, 2, 3): totals via feature 0
        dok2 = jnp.asarray(rng.random((s, 2)) < 0.8)
        find2 = pgrow.sibling_split_search(
            PGrowParams(2 * s - 1, b, f, 1000, -1, True, False), *_plain_meta_hyper(f, b),
            jnp.ones((f,), jnp.float32))

        @functools.partial(jax.jit, static_argnums=1)
        def search(n_act, batch):
            return pgrow.level_split_scan(find2, hists, sums2, dok2, n_act, batch, f, b)

        whole, visited = search(jnp.int32(s // 2), s)
        assert int(visited) == s
        gains = np.asarray(whole.gain)
        assert (gains > 0).any() and (gains == -np.inf).any()  # both kinds compared
        return search, whole

    @pytest.mark.parametrize("n_act", [0, 1, 7, 8, 9, SMAX // 2])
    @pytest.mark.parametrize("batch", [4, 8])
    def test_batched_search_is_the_whole_one_bit_for_bit(self, level, batch, n_act):
        search, whole = level
        got, visited = search(jnp.int32(n_act), batch)
        assert int(visited) == -(-n_act // batch) * batch
        for name, want, have in zip(whole._fields, whole, got):
            want, have = np.asarray(want), np.asarray(have)
            assert have.shape == want.shape == (self.SMAX, 2) and have.dtype == want.dtype
            # whole batches are searched (n_act <= visited); what no batch
            # reached was never written
            assert have[:int(visited)].tobytes() == want[:int(visited)].tobytes(), name
            assert not have[int(visited):].any(), name

    def test_the_batch_is_a_function_of_the_shape(self):
        from lightgbm_tpu.ops.pgrow import level_slots, scan_batch

        for cols, want in ((28, 256), (32, 256), (33, 128), (200, 32), (600, 8), (2000, 4),
                           (4000, 4)):
            assert scan_batch(255, pk.hist_lanes(cols, 63)) == want, cols
        assert scan_batch(31, pk.hist_lanes(28, 63)) == level_slots(31) == 32
        assert scan_batch(15, pk.hist_lanes(2000, 63)) == 4 < level_slots(15)

    @pytest.mark.parametrize("sharded", [False, True], ids=["serial", "four-shards"])
    def test_records_byte_equal_to_the_straight_search(self, monkeypatch, sharded):
        """End to end at a width where the shape rule itself picks a batch
        under SMAX (63 leaves x 600 columns: 8 of 64 slots, four trips at the
        fullest level): the chunk's records are those of the straight-line
        search byte for byte, serial and under a 4-device ``shard_map``, and
        ``scan_slots`` says which of the two ran."""
        from lightgbm_tpu.ops import pgrow

        if sharded and len(jax.devices()) < 4:
            pytest.skip("needs 4 devices")
        monkeypatch.setenv("LIGHTGBM_TPU_PGROW", "force")
        batched, pt = _wide_chunk(sharded)
        smax = pgrow.level_slots(63)
        sb = pgrow.scan_batch(63, pk.hist_lanes(600, 63))
        assert (smax, sb) == (64, 8)
        counts = pt.stream_counts(batched, 2)
        assert counts["levels"] * sb < counts["scan_slots"] < counts["levels"] * smax
        assert counts["level_segments"] <= counts["scan_slots"] <= (
            counts["level_segments"] + counts["levels"] * (sb - 1))
        # the same program with the straight-line search: the grower is traced
        # anew, under a budget that holds all SMAX slots in one batch
        monkeypatch.setattr(pgrow, "SCAN_BATCH_BYTES", 1 << 40)
        jax.clear_caches()
        straight, pt = _wide_chunk(sharded)
        jax.clear_caches()
        assert pt.stream_counts(straight, 2)["scan_slots"] == counts["levels"] * smax
        assert batched["num_splits"].tolist() == straight["num_splits"].tolist() == [[62]] * 2
        assert batched["raw"].tobytes() == straight["raw"].tobytes()
        assert batched["levels"][..., :3].tolist() == straight["levels"][..., :3].tolist()
