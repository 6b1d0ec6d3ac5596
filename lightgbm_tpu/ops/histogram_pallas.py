"""Pallas TPU histogram kernel — the device counterpart of the
reference's GPU histogram kernels (src/treelearner/ocl/histogram256.cl:345
per-workgroup sub-histograms + in-kernel reduction; host driver
src/treelearner/gpu_tree_learner.cpp:123-191).

Why not the XLA one-hot matmul (ops/histogram.py)?  XLA materializes the
(rows, F*B) one-hot operand through HBM — ~7 KB of traffic per row — which
measures at ~0.21 us/row on v5e.  Here the one-hot tile is built in VMEM,
fed straight to the MXU, and never touches HBM: the kernel streams only
the packed bin words + values (~44 B/row) and accumulates the (F*B, 4)
histogram in a VMEM scratch across sequential grid steps.

Input layout: one (C, S) int32 matrix `P` whose rows are
    [0..W)   : packed bin words (`per` bins of `bits` bits each per word)
    W        : grad  (f32 bitcast)
    W+1      : hess  (f32 bitcast)
    W+2      : select(f32 bitcast; 0/1 bagging x leaf mask)
(extra rows beyond W+3, e.g. a row-id payload, are ignored).  This is the
partitioned-data layout of ops/pgrow.py: a leaf's rows are a contiguous
column range, so the kernel only needs a [lo, hi) column mask — no gather.

Output: (F, B, 3) f32 of (sum_grad, sum_hess, count) per (feature, bin).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Columns (rows of data) per grid step.  The one-hot chunk is
# (FCHUNK*B, BLK) bf16; BLK=1024 with FCHUNK*B<=1024 keeps it <=2 MB.
BLK = 1024
_LANE = 128  # MXU/DMA lane quantum


def tune_fchunk(num_features: int, num_bins: int,
                max_tile_bytes: int = 2 * 1024 * 1024) -> int:
    """Feature-chunk width for the one-hot histogram dots, tuned against
    the (bin-count, feature-count) shape instead of the old fixed
    ``512 // num_bins`` rule.

    The kernel builds the bin one-hots as an (fchunk*B, BLK) bf16 tile
    and contracts it on the MXU.  Per 1024-row block the estimated cost
    is sum over chunks of roundup(chunk*B, 128) MXU rows (the systolic
    array pads the non-contracting dim to the 128-lane quantum) plus a
    fixed per-dot issue overhead — so the tuner prefers chunk widths
    whose row count is 128-aligned AND divide the feature count evenly
    (no ragged tail tile), under a VMEM tile budget.  Bit-safety: fchunk
    only groups which (feature, bin) cells share one dot_general; each
    cell still contracts the same BLK lanes in the same order, so ANY
    fchunk produces bit-identical histograms.

    ``LIGHTGBM_TPU_HIST_FCHUNK`` overrides (clamped to [1, F]); the
    split/level kernels call with a smaller ``max_tile_bytes`` because
    their VMEM is already crowded by the partition stream buffers.
    """
    env = os.environ.get("LIGHTGBM_TPU_HIST_FCHUNK", "")
    if env:
        try:
            return max(1, min(num_features, int(env)))
        except ValueError:
            pass
    cap = max(1, min(num_features, max_tile_bytes // max(num_bins * BLK * 2, 1)))
    best = max(
        range(1, cap + 1),
        key=lambda f: (-fchunk_cost(num_features, num_bins, f), f),
    )
    return best


def fchunk_cost(num_features: int, num_bins: int, fchunk: int) -> int:
    """Estimated per-block MXU row cost of a feature-chunk width: sum of
    128-padded one-hot rows over chunks plus a fixed per-dot issue
    overhead."""
    cost, rem, chunks = 0, num_features, 0
    while rem > 0:
        c = min(fchunk, rem)
        rem -= c
        chunks += 1
        cost += -(-c * num_bins // _LANE) * _LANE
    return cost + chunks * 256  # per-dot issue overhead (~2 lane rows)


def _hist_kernel(lohi_ref, p_ref, out_ref, acc_ref, *, nf, nb, rows, per, bits, fchunk):
    j = pl.program_id(0)
    g_row, h_row, sel_row = rows

    @pl.when(j == 0)
    def _init():
        acc_ref[:, :] = jnp.zeros_like(acc_ref)

    pos = jax.lax.broadcasted_iota(jnp.int32, (1, BLK), 1) + j * BLK
    valid = ((pos >= lohi_ref[0]) & (pos < lohi_ref[1])).astype(jnp.float32)
    g = pltpu.bitcast(p_ref[g_row : g_row + 1, :], jnp.float32)
    h = pltpu.bitcast(p_ref[h_row : h_row + 1, :], jnp.float32)
    sel = pltpu.bitcast(p_ref[sel_row : sel_row + 1, :], jnp.float32) * valid
    gs = g * sel
    hs = h * sel

    # The MXU's fast path is bf16xbf16->f32, but a bf16-rounded gradient
    # loses ~2^-8 relative accuracy per element (the reference's GPU kernel
    # keeps f32 accumulators for the same reason, histogram256.cl:345).
    # Because the dot's N dimension pads to 128 lanes regardless, extra
    # value rows are FREE: send each value as THREE bf16 terms
    # (x = hi + mid + lo, covering ~24 mantissa bits = f32 fidelity) and
    # re-sum the three output columns outside — f32 accuracy at bf16 speed.
    def split3(x):
        x_hi = x.astype(jnp.bfloat16)
        r1 = x - x_hi.astype(jnp.float32)
        x_mid = r1.astype(jnp.bfloat16)
        x_lo = (r1 - x_mid.astype(jnp.float32)).astype(jnp.bfloat16)
        return x_hi, x_mid, x_lo

    g3 = split3(gs)
    h3 = split3(hs)
    vals = jnp.concatenate(
        list(g3) + list(h3) + [sel.astype(jnp.bfloat16)], axis=0
    )  # (7, BLK) bf16

    mask_v = (1 << bits) - 1
    iota_b = jax.lax.broadcasted_iota(jnp.int32, (nb, BLK), 0)
    for c0 in range(0, nf, fchunk):
        c1 = min(c0 + fchunk, nf)
        chunks = []
        for f in range(c0, c1):
            w, p = divmod(f, per)
            byte = (p_ref[w : w + 1, :] >> (p * bits)) & mask_v  # (1, BLK)
            chunks.append((byte == iota_b).astype(jnp.bfloat16))  # (nb, BLK)
        oh = jnp.concatenate(chunks, axis=0)  # ((c1-c0)*nb, BLK)
        acc_ref[c0 * nb : c1 * nb, :] += jax.lax.dot_general(
            oh,
            vals,
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(j == pl.num_programs(0) - 1)
    def _flush():
        out_ref[:, :] = acc_ref[:, :]


@functools.partial(
    jax.jit,
    static_argnames=("num_features", "num_bins", "per", "bits", "rows", "interpret"),
)
def hist_segment(
    p: jnp.ndarray,
    lo: jnp.ndarray,
    hi: jnp.ndarray,
    num_features: int,
    num_bins: int,
    per: int = 4,
    bits: int = 8,
    rows: tuple = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """(F, B, 3) histogram of columns [lo, hi) of the packed matrix ``p``.

    p : (C, S) int32, S a multiple of BLK — see module docstring.
    lo, hi : int32 scalars — the valid column range (the leaf's segment,
      relative to this slice).  Columns outside contribute zero.
    rows : optional (g, h, sel) channel-row triple for matrices whose
      value rows are NOT at W..W+2 (the pgrow packed layout pads the bin
      words to 8 sublanes — pass ``PLayout.rows``).
    """
    c, s = p.shape
    assert s % BLK == 0, f"segment length {s} not a multiple of {BLK}"
    if rows is None:
        w_words = -(-num_features // per)
        rows = (w_words, w_words + 1, w_words + 2)
    fb = num_features * num_bins
    fchunk = tune_fchunk(num_features, num_bins)

    lohi = jnp.stack([lo.astype(jnp.int32), hi.astype(jnp.int32)])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(s // BLK,),
        in_specs=[
            pl.BlockSpec((c, BLK), lambda j, lohi: (0, j), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (fb, 7), lambda j, lohi: (0, 0), memory_space=pltpu.VMEM
        ),
        scratch_shapes=[pltpu.VMEM((fb, 7), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(
            _hist_kernel,
            nf=num_features,
            nb=num_bins,
            rows=rows,
            per=per,
            bits=bits,
            fchunk=fchunk,
        ),
        out_shape=jax.ShapeDtypeStruct((fb, 7), jnp.float32),
        grid_spec=grid_spec,
        interpret=interpret,
        name="hist_segment",
    )(lohi, p)
    # re-sum the 3-term splits: (sum_g, sum_h, count)
    hist = jnp.stack(
        [
            out[:, 0] + (out[:, 1] + out[:, 2]),
            out[:, 3] + (out[:, 4] + out[:, 5]),
            out[:, 6],
        ],
        axis=1,
    )
    return hist.reshape(num_features, num_bins, 3)


# ======================================================================
# hist_segments: multi-leaf segmented histograms, ONE kernel launch
# ======================================================================
def _hist_multi_kernel(sref, p_any, hist_out, acc2, buf_ref, rsem, hsem, *,
                       nf, nb, rows, c, fchunk, bits, fbp):
    """All ``n_active`` leaf segments' (F, B) histograms in one launch.

    Per-segment streaming copies _hist_kernel's double-buffered DMA
    pattern (ops/pkernels._hist_kernel); per-segment (8, F*B) results
    are DMA'd to the output double-buffered while the next segment
    streams — the per-leaf kernel-launch fixed cost (not measured on
    this machine) collapses to one launch per LEVEL.

    sref: (1 + smax, 2) int32 — row 0 holds [n_active, 0]; row 1+s holds
    segment s's [start, cnt]."""
    n_active = sref[0, 0]
    g_row, h_row, sel_row = rows
    per = 32 // bits
    mask = (1 << bits) - 1
    iota_b = jax.lax.broadcasted_iota(jnp.int32, (nb, BLK), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, BLK), 1)

    def one_seg(s, _):
        slot = jax.lax.rem(s, 2)

        # wait for the DMA that used this accumulator slot two segments ago
        @pl.when(s >= 2)
        def _():
            pltpu.make_async_copy(acc2.at[slot], acc2.at[slot], hsem.at[slot]).wait()

        acc2[slot] = jnp.zeros_like(acc2[slot])
        acc = acc2.at[slot]
        start = sref[1 + s, 0]
        cnt = sref[1 + s, 1]
        base = pl.multiple_of((start // BLK) * BLK, _LANE)
        head = start - base
        nblk = (head + cnt + BLK - 1) // BLK

        def get_dma(bslot, j):
            return pltpu.make_async_copy(
                p_any.at[:, pl.ds(base + j * BLK, BLK)], buf_ref.at[bslot],
                rsem.at[bslot],
            )

        @pl.when(nblk > 0)
        def _():
            get_dma(0, 0).start()

        def body(j, _):
            bslot = jax.lax.rem(j, 2)

            @pl.when(j + 1 < nblk)
            def _():
                get_dma(1 - bslot, j + 1).start()

            get_dma(bslot, j).wait()
            blk = buf_ref[bslot]
            pos = lane + j * BLK
            valid = ((pos >= head) & (pos < head + cnt)).astype(jnp.float32)
            sel = pltpu.bitcast(blk[sel_row : sel_row + 1, :], jnp.float32) * valid
            g = pltpu.bitcast(blk[g_row : g_row + 1, :], jnp.float32) * sel
            h = pltpu.bitcast(blk[h_row : h_row + 1, :], jnp.float32) * sel

            def split3(x):
                x_hi = x.astype(jnp.bfloat16)
                r1 = x - x_hi.astype(jnp.float32)
                x_mid = r1.astype(jnp.bfloat16)
                x_lo = (r1 - x_mid.astype(jnp.float32)).astype(jnp.bfloat16)
                return [x_hi, x_mid, x_lo]

            vals = jnp.concatenate(
                split3(g) + split3(h) + [sel.astype(jnp.bfloat16)], axis=0
            )
            for c0 in range(0, nf, fchunk):
                c1 = min(c0 + fchunk, nf)
                chunks = []
                for f in range(c0, c1):
                    wd, p4 = divmod(f, per)
                    byte = (blk[wd : wd + 1, :] >> (p4 * bits)) & mask
                    chunks.append((byte == iota_b).astype(jnp.bfloat16))
                oh = jnp.concatenate(chunks, axis=0)
                acc[0:7, c0 * nb : c1 * nb] += jax.lax.dot_general(
                    vals, oh, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            return 0

        jax.lax.fori_loop(0, nblk, body, 0)
        pltpu.make_async_copy(acc2.at[slot], hist_out.at[s], hsem.at[slot]).start()
        return 0

    jax.lax.fori_loop(0, n_active, one_seg, 0)

    @pl.when(n_active >= 1)
    def _():
        slot = jax.lax.rem(n_active - 1, 2)
        pltpu.make_async_copy(acc2.at[slot], acc2.at[slot], hsem.at[slot]).wait()

    @pl.when(n_active >= 2)
    def _():
        slot = jax.lax.rem(n_active - 2, 2)
        pltpu.make_async_copy(acc2.at[slot], acc2.at[slot], hsem.at[slot]).wait()


@functools.partial(
    jax.jit,
    static_argnames=("num_features", "num_bins", "bits", "rows", "smax", "interpret"),
)
def hist_segments(
    p: jnp.ndarray,
    seg_tab: jnp.ndarray,
    n_active,
    *,
    num_features: int,
    num_bins: int,
    bits: int = 8,
    rows: tuple = None,
    smax: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """(smax, F, B, 3) histograms of ``n_active`` leaf segments of the
    packed matrix ``p`` in ONE kernel launch — the multi-leaf form of
    ``hist_segment`` for level-batched growers (one launch covers every
    active leaf of a tree level instead of one launch per leaf).

    seg_tab : (smax, 2) int32 rows of [start, cnt] (disjoint segments).
      Output rows for s >= n_active are undefined.  ``p`` must
      have enough tail columns that every segment's covering BLK-blocks
      exist (the pgrow packed matrix carries a BLK tail for exactly
      this; otherwise pad columns to the next BLK multiple).
    rows : (g, h, sel) channel-row triple; defaults to the plain
      pack_columns layout (W, W+1, W+2).
    """
    c = p.shape[0]
    per = 32 // bits
    if rows is None:
        w_words = -(-num_features // per)
        rows = (w_words, w_words + 1, w_words + 2)
    fb = num_features * num_bins
    fbp = -(-fb // _LANE) * _LANE  # sliced VMEM refs must be lane-aligned
    fchunk = tune_fchunk(num_features, num_bins)
    hdr = jnp.zeros((1, 2), jnp.int32).at[0, 0].set(jnp.int32(n_active))
    sv = jnp.concatenate([hdr, seg_tab.astype(jnp.int32)], axis=0)
    out = pl.pallas_call(
        functools.partial(
            _hist_multi_kernel, nf=num_features, nb=num_bins, rows=rows,
            c=c, fchunk=fchunk, bits=bits, fbp=fbp,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                pltpu.VMEM((2, 8, fbp), jnp.float32),  # double-buffered acc
                pltpu.VMEM((2, c, BLK), jnp.int32),  # stream buffers
                pltpu.SemaphoreType.DMA((2,)),  # read sem
                pltpu.SemaphoreType.DMA((2,)),  # hist-out sem
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((smax, 8, fbp), jnp.float32),
        interpret=interpret,
        name="hist_segments",
    )(sv, p)
    out = out[:, :, :fb]
    hist = jnp.stack(
        [
            out[:, 0] + (out[:, 1] + out[:, 2]),
            out[:, 3] + (out[:, 4] + out[:, 5]),
            out[:, 6],
        ],
        axis=2,
    )  # (smax, F*B, 3)
    return hist.reshape(smax, num_features, num_bins, 3)


# ======================================================================
# quantized-training variant: exact int32 accumulation
# ======================================================================
def _digits256(v):
    """int32 (|v| < 2^15) -> balanced base-256 digits (lo, hi), each in
    [-128, 128] and therefore exact in bf16, with v == hi * 256 + lo."""
    lo = ((v + 128) & 255) - 128
    return lo, (v - lo) >> 8


def _hist_kernel_q(lohi_ref, p_ref, out_ref, acc_ref, *, nf, nb, rows, per,
                   bits, fchunk):
    """Integer twin of ``_hist_kernel`` for quantized training: the value
    rows hold int16 levels stored as plain int32 words (no f32 bitcast)
    and the accumulator is int32.  Mosaic has no int32 x int32 matmul
    (refused on the v5e: "Bad lhs/rhs type"), so the exact integer sums
    ride the same bf16 MXU path as the f32 kernel: each level is split
    into two base-256 digits (exact in bf16), a 1024-row block's digit
    sums stay under 2^17 (exact in the f32 dot result), and each block's
    result is cast to int32 before it is accumulated — no rounding
    anywhere, so the output is still order-invariant."""
    j = pl.program_id(0)
    g_row, h_row, sel_row = rows

    @pl.when(j == 0)
    def _init():
        acc_ref[:, :] = jnp.zeros_like(acc_ref)

    pos = jax.lax.broadcasted_iota(jnp.int32, (1, BLK), 1) + j * BLK
    valid = ((pos >= lohi_ref[0]) & (pos < lohi_ref[1])).astype(jnp.int32)
    sel = p_ref[sel_row : sel_row + 1, :] * valid  # int32 0/1
    planes = (_digits256(p_ref[g_row : g_row + 1, :] * sel)
              + _digits256(p_ref[h_row : h_row + 1, :] * sel) + (sel,))
    vals = jnp.concatenate(
        [x.astype(jnp.float32).astype(jnp.bfloat16) for x in planes], axis=0
    )  # (5, BLK) bf16: g_lo, g_hi, h_lo, h_hi, sel

    mask_v = (1 << bits) - 1
    iota_b = jax.lax.broadcasted_iota(jnp.int32, (nb, BLK), 0)
    for c0 in range(0, nf, fchunk):
        c1 = min(c0 + fchunk, nf)
        chunks = []
        for f in range(c0, c1):
            w, p = divmod(f, per)
            byte = (p_ref[w : w + 1, :] >> (p * bits)) & mask_v
            chunks.append((byte == iota_b).astype(jnp.bfloat16))
        oh = jnp.concatenate(chunks, axis=0)  # ((c1-c0)*nb, BLK) bf16
        acc_ref[c0 * nb : c1 * nb, :] += jax.lax.dot_general(
            oh,
            vals,
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(jnp.int32)

    @pl.when(j == pl.num_programs(0) - 1)
    def _flush():
        out_ref[:, :] = acc_ref[:, :]


@functools.partial(
    jax.jit,
    static_argnames=("num_features", "num_bins", "per", "bits", "rows", "interpret"),
)
def hist_segment_q(
    p: jnp.ndarray,
    lo: jnp.ndarray,
    hi: jnp.ndarray,
    num_features: int,
    num_bins: int,
    per: int = 4,
    bits: int = 8,
    rows: tuple = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """(F, B, 3) EXACT int32 histogram of columns [lo, hi) of a
    quantized packed matrix (``pack_columns_q``) — the quantized-training
    twin of :func:`hist_segment`.  The output is order-invariant by
    construction (integer adds).  Levels must fit int16."""
    c, s = p.shape
    assert s % BLK == 0, f"segment length {s} not a multiple of {BLK}"
    if rows is None:
        w_words = -(-num_features // per)
        rows = (w_words, w_words + 1, w_words + 2)
    fb = num_features * num_bins
    fchunk = tune_fchunk(num_features, num_bins)

    lohi = jnp.stack([lo.astype(jnp.int32), hi.astype(jnp.int32)])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(s // BLK,),
        in_specs=[
            pl.BlockSpec((c, BLK), lambda j, lohi: (0, j), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (fb, 5), lambda j, lohi: (0, 0), memory_space=pltpu.VMEM
        ),
        scratch_shapes=[pltpu.VMEM((fb, 5), jnp.int32)],
    )
    out = pl.pallas_call(
        functools.partial(
            _hist_kernel_q,
            nf=num_features,
            nb=num_bins,
            rows=rows,
            per=per,
            bits=bits,
            fchunk=fchunk,
        ),
        out_shape=jax.ShapeDtypeStruct((fb, 5), jnp.int32),
        grid_spec=grid_spec,
        interpret=interpret,
        name="hist_segment_q",
    )(lohi, p)
    hist = jnp.stack(
        [out[:, 0] + (out[:, 1] << 8), out[:, 2] + (out[:, 3] << 8), out[:, 4]],
        axis=1,
    )
    return hist.reshape(num_features, num_bins, 3)


def pack_columns_q(bins, qgrad, qhess, select, per: int = 4, bits: int = 8):
    """Quantized twin of :func:`pack_columns`: the value rows carry the
    int16 levels (and the 0/1 select) widened to plain int32 words —
    integer identity, no bitcasting."""
    n, f = bins.shape
    w = -(-f // per)
    pad_f = w * per - f
    bb = jnp.pad(bins.astype(jnp.int32), ((0, 0), (0, pad_f)))
    bb = bb.reshape(n, w, per)
    shifts = (jnp.arange(per) * bits).astype(jnp.int32)
    words = jnp.sum(bb << shifts[None, None, :], axis=2, dtype=jnp.int32)
    rows = [
        words.T,
        qgrad.astype(jnp.int32)[None, :],
        qhess.astype(jnp.int32)[None, :],
        select.astype(jnp.int32)[None, :],
    ]
    return jnp.concatenate(rows, axis=0)


def pack_columns(
    bins, grad, hess, select, row_id=None, per: int = 4, bits: int = 8
):
    """Build the (C, N) int32 packed matrix from (N, F) bins + value
    vectors.  Rows: W bin words, grad, hess, select[, row_id]."""
    n, f = bins.shape
    w = -(-f // per)
    pad_f = w * per - f
    bb = jnp.pad(bins.astype(jnp.int32), ((0, 0), (0, pad_f)))
    bb = bb.reshape(n, w, per)
    shifts = (jnp.arange(per) * bits).astype(jnp.int32)
    words = jnp.sum(bb << shifts[None, None, :], axis=2, dtype=jnp.int32)  # (N, W)
    rows = [
        words.T,
        jax.lax.bitcast_convert_type(grad.astype(jnp.float32), jnp.int32)[None, :],
        jax.lax.bitcast_convert_type(hess.astype(jnp.float32), jnp.int32)[None, :],
        jax.lax.bitcast_convert_type(select.astype(jnp.float32), jnp.int32)[None, :],
    ]
    if row_id is not None:
        rows.append(row_id.astype(jnp.int32)[None, :])
    return jnp.concatenate(rows, axis=0)
