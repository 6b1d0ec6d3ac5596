"""Dynamic-segment Pallas kernels for the partitioned tree grower.

TPU-native counterpart of the reference's histogram kernels and data
partition (src/treelearner/ocl/histogram256.cl:345 per-workgroup
sub-histograms + reduction, host driver gpu_tree_learner.cpp:123-191;
src/treelearner/data_partition.hpp:94-150 ``Split``).

The training matrix ``P`` is one (C, N) int32 array whose rows are

    0..W-1      : packed bin words, 4 uint8 bins per int32 (W = ceil(F/4))
    W..WPAD-1   : padding (WPAD = W rounded up to 8 sublanes)
    WPAD + 0    : grad   (f32 bitcast)
    WPAD + 1    : hess   (f32 bitcast)
    WPAD + 2    : select (f32 bitcast; 0/1 bagging mask)
    WPAD + 3..  : score channel(s), label, row id, weight — an 8-aligned
                  "mutable band" so the in-place channel-update kernel can
                  DMA it as one aligned row block.

Rows are kept PHYSICALLY PARTITIONED by leaf: each leaf owns a
contiguous column range [start, start+cnt).  That gives the reference's
DataPartition asymptotics (O(N_leaf) per split, not O(N)) without any
gather (streaming DMA + MXU against a row gather; neither rate is
measured on this machine).

Two backend observations shape this file (the first made on a v5e under a
retired runtime and re-met in PR 27, the second measured in PR 37):
  1. ANY XLA-level write to the 64 MB packed matrix — even a one-element
     `.at[0,0].add(1)` on a donated loop carry — triggered a whole-array
     copy.  Only Pallas kernels with ``input_output_aliases`` mutate it
     truly in place.  The resolution
     is a carry-layout contract, not donation avoidance: the matrix
     travels the fused loop carry untouched by XLA ops (every mutation
     is an aliased Pallas pass; all scalar/per-leaf bookkeeping lives in
     SEPARATE small carry arrays), and the jitted kernel entry points
     here (``split_stream``/``level_stream``/``score_add``) carry
     ``donate_argnums=(0,)`` so standalone calls alias straight through
     instead of paying a defensive input copy.  The contract covers
     control flow too (PR 27, measured on a v5e: 53% of a 21M-row
     iteration was such copies): no ``lax.cond`` may take or return the
     matrix, because copy insertion then copies it whole in the
     pass-through branch and twice in every loop body nested inside; a
     caller with nothing to do launches the kernel on the empty segment
     ``(0, 0)`` instead (ops/pgrow.py).  ``update_channels`` /
     ``score_add`` stream only the 8-aligned mutable band for
     score/gradient maintenance — the bin words are never re-read or
     re-written by a pass that doesn't need them.
  2. The streaming kernels wait for the MXU to take in one-hot WEIGHT
     TILES, not for HBM and not for the compares that build them (v5e,
     PR 37, ``level_stream`` alone on a 21M x 16 matrix).  A 1,024-row
     block's 128 KB in and out are 0.16 us of HBM; the block took 5.45 us:
     1.45 for the histogram's 112 tiles of 128 x 128 (14 streamed rows
     each), 3.35 for the 192 tiles of a dense (BLK, BLK) permutation and
     running count (~105 cycles a tile on each of four MXUs, whatever the
     tile holds), 0.65 for the rest (DMA waits, the split predicate, the
     merge under the carries, the stage copies).  So the compaction
     multiplies only the tiles its one-hot can be non-zero in
     (``_staircase``: 33 tiles, 0.76 us, of it 0.12 the count, the 16
     window offsets that leave the vector unit as scalars and the
     one-hots' compares) and a block takes 2.84 us, half of it the
     histogram's.  Histogram work is fused INTO the partition pass
     (``split_stream``): the partition must stream the parent segment
     anyway, and both children's histograms only widen the MXU operand
     from 7 to 14 sublanes: the same weight tiles.

Width (PR 29).  Every kernel here is ``grid=(1,)`` with hand-written DMA
over whole (C, BLK) blocks, and a block is 2 MB at 2,000 columns (C =
512), so no kernel loads one as a value.  A block stays in its VMEM
buffer and the COMPUTE walks it in groups: the histograms in column
groups of _HIST_GROUP_WORDS word rows (``col_groups``: a rolled loop
over full groups, then what is left as one static group — all there is
up to 31 columns), the permutation in groups of _PERM_GROUP_ROWS channel
rows (``_for_row_groups``).  Program size therefore does not grow with
the columns, and each kernel asks Mosaic for the VMEM its buffers need
(``_vmem_params``; the histograms of ALL groups stay in VMEM: 16 MB of
the v5e's 128 MiB at 2,000 columns x 63 bins).  A column's bins sit on a
pitch of ``bin_pitch`` lanes in a histogram row (63 -> 64), so that a
group starts on a lane tile whatever the bin count; ``_hist_from_rows``
drops the padding cells, which stay zero.  Grouping decides only which
cells share a loop trip: histograms are bit-identical under any.

``split_stream`` replaces the old partition + copy-back + child-histogram
trio with ONE pass: a two-ended in-place partition (blocks are consumed
from both ends of the segment so vacated space always precedes the write
frontiers — the protocol is simulated exhaustively in
tests/test_pgrow.py) that accumulates (Σg, Σh, Σsel) per (feature, bin)
for the left AND right children while each block is resident in VMEM.
It needs NO scratch copy of the matrix (the old design kept a second
full-size buffer: 670 MB at Higgs scale) and halves per-split traffic.

Why matmuls everywhere: Mosaic has no vector scatter/gather and no
cumsum, and a one-hot matmul is both; what it costs is the weight tiles
it hands the MXU (observation 2), so each is kept to the tiles that can
hold a one.  So
- cumsum(goes_left) = within a lane tile, one dot with a (128, 128)
  triangular ones matrix (every tile's flags stacked on sublanes: one
  weight tile a block); across tiles, a scalar prefix of the tile totals,
- the in-block compaction is a one-hot permutation matmul applied to the
  block's four byte planes (integers 0..255 are exact in bf16, so the
  permutation is bit-exact on int32/f32 data); a stable compaction's
  one-hot is a staircase, one source lane tile lands in at most 128
  consecutive lanes, so it is built and multiplied as a two-tile window a
  source tile (``_staircase``, ``_apply_staircase``),
- per-bin accumulation = dot of bf16 value rows with bin-equality
  one-hots (3-term hi/mid/lo value split keeps f32 fidelity),
exactly the trade SURVEY §7 prescribes (scatter -> one-hot matmul).

Within-leaf row ORDER is not preserved (the two-ended scheme interleaves
front and back blocks).  Nothing downstream depends on it: histograms,
leaf sums and segment score updates are permutation-invariant, and the
original row index travels in the ROWID channel for prediction/eval
unscrambling.  (The reference's DataPartition::Split is stable, but no
consumer of that stability exists there either — it falls out of its
per-thread buffer merge.)
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .histogram_pallas import tune_fchunk

BLK = 1024  # columns (data rows) per streamed chunk
_LANE = 128  # DMA lane-alignment quantum
_RING = 3  # read-buffer ring depth per stream end (max occupancy 2 + 1 inflight)
_HIST_GROUP_WORDS = 8  # bin-word rows a rolled histogram group holds (one sublane tile)
_PERM_GROUP_ROWS = 128  # channel rows a rolled permutation group holds
_TILES = BLK // _LANE  # lane tiles of a block: what the in-block compaction walks
# one-hot tile budget of the kernels whose VMEM the partition stream buffers
# crowd (split_stream, level_stream): the historical 1 MiB
_SPLIT_TILE_BYTES = 1024 * 1024
_UPDATE_TILE_BYTES = 2 * 1024 * 1024  # tune_fchunk's default, the other kernels'
# Mosaic's scoped VMEM default fits the kernels' scratch up to a few hundred
# columns.  Past _VMEM_DEFAULT_FITS of scratch the limit is asked for: the
# scratch plus room for the values Mosaic spills there (a one-hot tile, a row
# group's byte planes and their dots; compiled for the v5e at 2,000 and 3,900
# columns, PR 37: the kernels need between 2 and 4 MiB of it)
_VMEM_DEFAULT_FITS = 6 * 1024 * 1024
_VMEM_SPILL_ROOM = 16 * 1024 * 1024


def num_words(num_features: int, bits: int = 8) -> int:
    return -(-num_features // (32 // bits))


class PLayout:
    """Channel-row indices inside the packed matrix.

    ``bits`` selects the bin word width: 8 (4 bins/int32) for max_bin up
    to 256, or 4 (8 bins/int32) when every column fits 16 bins — the TPU
    form of the reference's Dense4bitsBin (dense_nbits_bin.hpp:37),
    halving resident bin bytes and per-row stream traffic.

    The mutable rows (grad/hess/select/scores + label/rowid/weight) live
    in an 8-sublane-aligned band starting at WPAD so ``update_channels``
    can DMA-slice them (Mosaic requires row-slice shapes and offsets
    aligned to the (8, 128) tile)."""

    def __init__(self, num_features: int, num_score: int = 1, with_weight: bool = True,
                 bits: int = 8):
        self.F = num_features
        self.bits = bits
        self.per = 32 // bits
        self.W = num_words(num_features, bits)
        self.WPAD = -(-self.W // 8) * 8
        # K grad/hess row PAIRS (multiclass trains K trees per iteration
        # from K gradient planes computed once per iteration —
        # GBDT::Boosting, gbdt.cpp:692-700); K == 1 reproduces the
        # classic G/H/SEL/SCORE ordering exactly.
        K = num_score
        self.G = self.WPAD  # class-0 pair (g_row(0)/h_row(0))
        self.H = self.WPAD + 1
        self.SEL = self.WPAD + 2 * K
        self.SCORE = self.SEL + 1  # .. SCORE + num_score - 1
        self.num_score = num_score
        self.LABEL = self.SCORE + num_score
        self.ROWID = self.LABEL + 1
        self.WEIGHT = self.ROWID + 1 if with_weight else -1
        self.with_weight = with_weight
        band = 2 * K + 1 + num_score + 2 + (1 if with_weight else 0)
        self.BAND = -(-band // 8) * 8
        self.C = self.WPAD + self.BAND

    def g_row(self, k: int) -> int:
        return self.WPAD + 2 * k

    def h_row(self, k: int) -> int:
        return self.WPAD + 2 * k + 1

    def class_rows(self, k: int):
        """(g, h, sel) row triple for class k — static kernel param."""
        return (self.g_row(k), self.h_row(k), self.SEL)

    @property
    def rows(self):
        """(g, h, sel) row indices for class 0."""
        return (self.G, self.H, self.SEL)


def num_channels(num_features: int, num_score: int = 1, with_weight: bool = True,
                 bits: int = 8) -> int:
    return PLayout(num_features, num_score, with_weight, bits).C


def pack_matrix(bins: np.ndarray, layout: PLayout, label=None, weight=None,
                num_real=None) -> jnp.ndarray:
    """Build the (C, N + BLK) packed matrix from (N, F) uint8 bins.

    The BLK tail columns absorb block-granular DMA overruns.  grad/hess
    start at 0, select at 1, scores at 0; rowid is the original row
    index (prediction / eval unscrambling).  Rows >= ``num_real`` are
    shard-padding dummies: select stays 0 so they never enter a
    histogram (Metadata::CheckOrPartition's equal-shard padding)."""
    n, f = bins.shape
    assert f == layout.F
    assert bins.dtype == np.uint8, "partitioned path requires max_bin <= 256"
    assert int(bins.max(initial=0)) < (1 << layout.bits), (
        f"bin values exceed the {layout.bits}-bit word field"
    )
    nr = n if num_real is None else int(num_real)
    w, per, bits = layout.W, layout.per, layout.bits
    pad_f = w * per - f
    bb = np.pad(np.asarray(bins), ((0, 0), (0, pad_f))).astype(np.uint32)
    bb = bb.reshape(n, w, per)
    words = np.zeros((n, w), np.uint32)
    for k in range(per):
        words |= bb[:, :, k] << (bits * k)
    words = words.view(np.int32)
    P = np.zeros((layout.C, n + BLK), np.int32)
    P[:w, :n] = words.T
    one = np.float32(1.0).view(np.int32)
    P[layout.SEL, :nr] = one
    if label is not None:
        P[layout.LABEL, :n] = np.asarray(label, np.float32).view(np.int32)
    P[layout.ROWID, :n] = np.arange(n, dtype=np.int32)
    if layout.with_weight:
        wv = np.ones(n, np.float32) if weight is None else np.asarray(weight, np.float32)
        P[layout.WEIGHT, :n] = wv.view(np.int32)
    return jnp.asarray(P)


def pack_matrix_device(bins_dev, layout: PLayout, label=None, weight=None) -> jnp.ndarray:
    """pack_matrix built ON DEVICE from an already-transferred (N, F)
    uint8 bins array: shipping the 28 B/row bins once and deriving the
    packed matrix with XLA shifts moves less over the host link than
    shipping the 64 B/row matrix (link rate not measured on this machine)."""
    n, f = bins_dev.shape
    w, per, bits = layout.W, layout.per, layout.bits
    pad_f = w * per - f
    bb = jnp.pad(bins_dev.astype(jnp.int32), ((0, 0), (0, pad_f)))
    # mask defensively: an oversized bin value would OR into the next
    # feature's field (callers guarantee the bound; this keeps corruption
    # local to the offending feature instead of silent cross-talk)
    bb = bb & ((1 << bits) - 1)
    bb = bb.reshape(n, w, per)
    shifts = (jnp.arange(per, dtype=jnp.int32) * bits)[None, None, :]
    words = jnp.sum(bb << shifts, axis=2, dtype=jnp.int32)  # (N, W)
    one = np.float32(1.0).view(np.int32)

    def frow(x):
        return jax.lax.bitcast_convert_type(jnp.asarray(x, jnp.float32), jnp.int32)

    rows = [words.T]
    if layout.WPAD > w:
        rows.append(jnp.zeros((layout.WPAD - w, n), jnp.int32))
    rows.append(jnp.zeros((2 * layout.num_score, n), jnp.int32))  # g/h pairs
    rows.append(jnp.full((1, n), one, jnp.int32))  # sel
    rows.append(jnp.zeros((layout.num_score, n), jnp.int32))  # scores
    rows.append(frow(label if label is not None else np.zeros(n, np.float32))[None, :])
    rows.append(jnp.arange(n, dtype=jnp.int32)[None, :])  # rowid
    if layout.with_weight:
        wv = jnp.ones((n,), jnp.float32) if weight is None else jnp.asarray(weight, jnp.float32)
        rows.append(jax.lax.bitcast_convert_type(wv, jnp.int32)[None, :])
    p = jnp.concatenate(rows, axis=0)
    cpad = layout.C - p.shape[0]
    return jnp.pad(p, ((0, cpad), (0, BLK)))


def _planes(blk_i32):
    """(C, BLK) int32 -> (4C, BLK) bf16 byte planes (exact in bf16)."""
    ps = [(blk_i32 >> (8 * k)) & 255 for k in range(4)]
    return jnp.concatenate(ps, axis=0).astype(jnp.bfloat16)


def _unplanes(dots_f32, c):
    """(4C, BLK) f32 byte planes -> (C, BLK) int32 (exact repack)."""
    p = dots_f32.astype(jnp.int32)
    return (
        p[0 * c : 1 * c]
        | (p[1 * c : 2 * c] << 8)
        | (p[2 * c : 3 * c] << 16)
        | (p[3 * c : 4 * c] << 24)
    )


def _split3(x):
    """f32 -> 3 bf16 planes (hi, mid, lo): f32 fidelity at bf16 matmul
    speed; the dot's sublane dim pads to 128 so extra rows are free."""
    hi = x.astype(jnp.bfloat16)
    r1 = x - hi.astype(jnp.float32)
    mid = r1.astype(jnp.bfloat16)
    lo = (r1 - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return [hi, mid, lo]


# ======================================================================
# column groups: what lets the streaming kernels pass any width
# ======================================================================
class ColGroups(NamedTuple):
    """How a streaming kernel walks the bin words of one (C, BLK) block when
    it builds a histogram: ``n_full`` groups of ``gw`` word rows in a ROLLED
    loop (program size does not grow with the columns), then the ``tail_w``
    words that are left as one static group.  Up to a group's worth of
    columns (28 columns: 7 words) the static group is all there is.  ANY
    grouping gives bit-identical histograms: a group only decides which
    (feature, bin) cells share a loop trip; each cell still contracts the
    same BLK lanes in the same order."""

    gw: int
    n_full: int
    tail_w: int

    @property
    def count(self) -> int:
        return self.n_full + (1 if self.tail_w else 0)


def col_groups(num_features: int, bits: int = 8) -> ColGroups:
    """Derived from the shape alone, as ``tune_fchunk`` derives its chunk."""
    gw = _HIST_GROUP_WORDS
    n_full = num_features // (gw * (32 // bits))  # groups whose columns all exist
    return ColGroups(gw, n_full, num_words(num_features, bits) - n_full * gw)


def bin_pitch(num_bins: int) -> int:
    """Lanes a column takes in a kernel's histogram row: its bins padded to
    the sublane tile (63 -> 64), so that a column's one-hot rows stack on
    tile boundaries and a group of _HIST_GROUP_WORDS words starts on a lane
    tile of the accumulator whatever the bin count.  A padding cell's
    one-hot row never matches: it stays zero, and ``_hist_from_rows`` drops
    it.  (Keeping the pitch at the bin count while one static group holds
    every column was tried on the chip, PR 29: level_stream then took 12%
    longer at 28 columns than with the padded pitch.)"""
    return -(-num_bins // 8) * 8


def hist_lanes(num_features: int, num_bins: int) -> int:
    """Lanes of one histogram row as the kernels issue it: F columns of
    ``bin_pitch`` cells, padded to the lane tile."""
    return -(-num_features * bin_pitch(num_bins) // _LANE) * _LANE


def _group_fchunk(nfeat: int, pitch: int, max_tile_bytes: int) -> int:
    """tune_fchunk for a full group: every chunk must start on a lane tile,
    because the group's own start is dynamic."""
    f = tune_fchunk(nfeat, pitch, max_tile_bytes=max_tile_bytes)
    if f >= nfeat or (f * pitch) % _LANE == 0:
        return f
    return max((k for k in range(1, f) if (k * pitch) % _LANE == 0), default=nfeat)


def _onehot_dots(words, vals, acc_ref, lane0, nfeat, *, pitch, bits, fchunk, iota_b):
    """acc[0:nv, lane0 + f*pitch + b] += sum over lanes of vals * [bin f == b]
    for the ``nfeat`` columns packed in ``words`` ((rows, BLK) int32), in
    static chunks of ``fchunk`` columns: an (fchunk*pitch, BLK) bf16 one-hot
    tile contracted on the MXU with the value rows on sublanes, so the
    accumulator is (nv, F*pitch): lane-major, which copies out clean (an
    (F*B, nv) output pays a strided VMEM->HBM copy measured at ~2 ms)."""
    nv = vals.shape[0]
    per = 32 // bits
    mask = (1 << bits) - 1
    for c0 in range(0, nfeat, fchunk):
        c1 = min(c0 + fchunk, nfeat)
        chunks = []
        for f in range(c0, c1):
            wd, p4 = divmod(f, per)
            byte = (words[wd : wd + 1, :] >> (p4 * bits)) & mask
            chunks.append((byte == iota_b).astype(jnp.bfloat16))
        oh = jnp.concatenate(chunks, axis=0)
        if isinstance(lane0, int):
            lanes = slice(lane0 + c0 * pitch, lane0 + c1 * pitch)
        else:
            lanes = pl.ds(pl.multiple_of(lane0 + c0 * pitch, _LANE), (c1 - c0) * pitch)
        acc_ref[0:nv, lanes] += jax.lax.dot_general(
            vals, oh, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )


def _hist_accumulate(blk_ref, vals, acc_ref, *, nf, nb, bits, max_tile_bytes):
    """One resident block's share of a histogram: ``acc_ref[0:nv, f*pitch + b]
    +=`` the one-hot dots of every column with the (nv, BLK) bf16 value
    rows, column group by column group (``col_groups``).  ``blk_ref`` is
    the (C, BLK) block in VMEM; only a group's word rows are loaded at a
    time."""
    grp = col_groups(nf, bits)
    gf = grp.gw * (32 // bits)
    pitch = bin_pitch(nb)
    iota_b = jax.lax.broadcasted_iota(jnp.int32, (pitch, BLK), 0)
    if grp.n_full:
        fchunk = _group_fchunk(gf, pitch, max_tile_bytes)

        def one_group(i, _):
            words = blk_ref[pl.ds(pl.multiple_of(i * grp.gw, 8), grp.gw), :]
            _onehot_dots(words, vals, acc_ref, i * (gf * pitch), gf, pitch=pitch,
                         bits=bits, fchunk=fchunk, iota_b=iota_b)
            return 0

        jax.lax.fori_loop(0, grp.n_full, one_group, 0)
    if grp.tail_w:
        w0 = grp.n_full * grp.gw
        tail_f = nf - grp.n_full * gf
        words = blk_ref[w0 : w0 + -(-grp.tail_w // 8) * 8, :]
        _onehot_dots(words, vals, acc_ref, grp.n_full * gf * pitch, tail_f,
                     pitch=pitch, bits=bits, iota_b=iota_b,
                     fchunk=tune_fchunk(tail_f, pitch, max_tile_bytes=max_tile_bytes))


def _for_row_groups(c: int, fn) -> None:
    """``fn(rows)`` over all ``c`` channel rows of a block, ``rows`` an
    index for the sublane axis: whole groups of _PERM_GROUP_ROWS in a rolled
    loop, then what is left (everything, up to one group) statically."""
    rg = _PERM_GROUP_ROWS
    n_full = c // rg if c > rg else 0
    if n_full:
        def one_group(i, _):
            fn(pl.ds(pl.multiple_of(i * rg, 8), rg))
            return 0

        jax.lax.fori_loop(0, n_full, one_group, 0)
    if c > n_full * rg:
        fn(slice(n_full * rg, c))


def _vmem_params(*scratch_bytes):
    """Compiler parameters for a kernel whose VMEM scratch comes to
    ``sum(scratch_bytes)``: none while the scoped default holds it (every
    shape up to a few hundred columns), else the limit it needs (the v5e
    has 128 MiB; at 2,000 columns level_stream asks for ~58 MiB)."""
    need = int(sum(scratch_bytes))
    if need <= _VMEM_DEFAULT_FITS:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=need + _VMEM_SPILL_ROOM)


def _tile_bytes(c: int, n: int = 1) -> int:
    return n * c * BLK * 4


def _planes_from_rows(out, row0=0):
    """(Σ 3-term g, Σ 3-term h, cnt) kernel rows of ``hist_lanes`` lanes,
    (..., rows, lanes) -> the g, h and count planes, (..., lanes) each, one
    lane a cell."""
    def row(i):
        return out[..., row0 + i, :]

    return (row(0) + (row(1) + row(2)), row(3) + (row(4) + row(5)), row(6))


def sibling_planes(out):
    """``split_stream``'s kernel rows, or every slot's of ``level_stream``,
    (..., 16, lanes) -> both children's g, h and count planes, three arrays
    (..., 2, lanes), left child then right: the left's seven rows and the
    right's seven are one axis folded in two, so no plane is stacked."""
    rows = out[..., :14, :]
    return _planes_from_rows(rows.reshape(rows.shape[:-2] + (2, 7, rows.shape[-1])))


def _hist_from_rows(out, num_features, num_bins, row0=0):
    """Those kernel rows -> (F, B, 3) histogram."""
    return _hist_cells(*_planes_from_rows(out, row0), num_features, num_bins)


def _hist_cells(g, h, cnt, num_features, num_bins):
    pitch = bin_pitch(num_bins)
    hist = jnp.stack([g, h, cnt], axis=1)[: num_features * pitch]
    return hist.reshape(num_features, pitch, 3)[:, :num_bins]


def plane_cells(planes, num_features, num_bins):
    """Planes of ``hist_lanes`` lanes, (..., lanes) -> (..., F, B): every
    column's ``bin_pitch`` lanes less their padding, the bins still on the
    minor axis.  What the fused engine's split search reads
    (ops/split.py ``best_split_planes``); ``_hist_cells`` is the (F, B, 3)
    form of the same cells, for the root and for ``hist_dyn``'s callers."""
    pitch = bin_pitch(num_bins)
    cells = planes[..., : num_features * pitch]
    return cells.reshape(planes.shape[:-1] + (num_features, pitch))[..., :num_bins]


# ======================================================================
# histogram kernel (root histogram / standalone segments)
# ======================================================================
def _hist_kernel(sref, p_any, o_ref, acc_ref, buf_ref, sem, *, nf, nb, rows, bits):
    start = sref[0]
    cnt = sref[1]
    g_row, h_row, sel_row = rows
    base = pl.multiple_of((start // BLK) * BLK, _LANE)
    head = start - base
    nblk = (head + cnt + BLK - 1) // BLK
    acc_ref[:, :] = jnp.zeros_like(acc_ref)

    def get_dma(slot, j):
        return pltpu.make_async_copy(
            p_any.at[:, pl.ds(base + j * BLK, BLK)], buf_ref.at[slot], sem.at[slot]
        )

    get_dma(0, 0).start()

    lane = jax.lax.broadcasted_iota(jnp.int32, (1, BLK), 1)

    def body(j, _):
        slot = jax.lax.rem(j, 2)

        @pl.when(j + 1 < nblk)
        def _():
            get_dma(1 - slot, j + 1).start()

        get_dma(slot, j).wait()
        blk = buf_ref.at[slot]
        pos = lane + j * BLK
        valid = ((pos >= head) & (pos < head + cnt)).astype(jnp.float32)
        sel = pltpu.bitcast(blk[sel_row : sel_row + 1, :], jnp.float32) * valid
        g = pltpu.bitcast(blk[g_row : g_row + 1, :], jnp.float32) * sel
        h = pltpu.bitcast(blk[h_row : h_row + 1, :], jnp.float32) * sel

        vals = jnp.concatenate(
            _split3(g) + _split3(h) + [sel.astype(jnp.bfloat16)], axis=0
        )
        _hist_accumulate(blk, vals, acc_ref, nf=nf, nb=nb, bits=bits,
                         max_tile_bytes=_UPDATE_TILE_BYTES)
        return 0

    jax.lax.fori_loop(0, nblk, body, 0)
    o_ref[:, :] = acc_ref[:, :]


@functools.partial(jax.jit, static_argnames=("num_features", "num_bins", "bits", "rows", "interpret"))
def hist_dyn(p, start, cnt, num_features, num_bins, bits=8, rows=None, interpret=False):
    """(F, B, 3) histogram of the leaf segment [start, start+cnt) of the
    packed matrix ``p`` — DenseBin::ConstructHistogram (dense_bin.hpp:66)
    over the leaf's contiguous rows, streamed at HBM bandwidth.  bits=4
    streams the Dense4bitsBin-packed form (8 bins per word).  ``rows``
    is the (g, h, sel) channel-row triple (PLayout.rows); defaults to the
    standard layout for ``num_features``."""
    if rows is None:
        wpad = -(-num_words(num_features, bits) // 8) * 8
        rows = (wpad, wpad + 1, wpad + 2)
    c = p.shape[0]
    fb = hist_lanes(num_features, num_bins)
    out = pl.pallas_call(
        functools.partial(_hist_kernel, nf=num_features, nb=num_bins, rows=rows, bits=bits),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[
                pltpu.VMEM((8, fb), jnp.float32),
                pltpu.VMEM((2, c, BLK), jnp.int32),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((8, fb), jnp.float32),
        compiler_params=_vmem_params(_tile_bytes(c, 2), 2 * 8 * fb * 4),
        interpret=interpret,
        name="hist_dyn",
    )(jnp.stack([jnp.int32(start), jnp.int32(cnt)]), p)
    return _hist_from_rows(out, num_features, num_bins)


# ======================================================================
# update_and_root_hist: fused channel refresh + root histogram
# ======================================================================
def _upd_hist_kernel(sref, aux_any, p_any_in, p_any, o_ref, acc_ref, buf_ref, abuf,
                     stage, rsem, asem, wsem, *, nf, nb, band0,
                     bits, grad_fn, lay_rows, use_sel, use_mul,
                     use_weight, n_delta, with_hist=True):
    """One streaming pass over ALL rows: score += delta, (g, h) =
    grad_fn(score, label, weight), select = sel, the block's mutable band
    written back in place (the bin words are read and never rewritten),
    AND the root (F, B, 3) histogram accumulated from the fresh values.
    Structurally a copy of _hist_kernel (its DMA pattern measures at full
    HBM bandwidth) plus a _stream_flush write-back of the band.

    ``lay_rows`` = (G, H, SEL, SCORE, LABEL, ROWID, WEIGHT) absolute row
    indices; ``band0`` the first row of the band they sit in."""
    n = sref[0]
    G_, H_, SEL_, SCORE_, LABEL_, ROWID_, WEIGHT_ = (r - band0 for r in lay_rows)
    bandn = stage.shape[1]
    nblk = (n + BLK - 1) // BLK
    if with_hist:
        acc_ref[:, :] = jnp.zeros_like(acc_ref)

    def get_dma(slot, j):
        return pltpu.make_async_copy(
            p_any.at[:, pl.ds(j * BLK, BLK)], buf_ref.at[slot], rsem.at[slot]
        )

    def get_aux(slot, j):
        return pltpu.make_async_copy(
            aux_any.at[:, pl.ds(j * BLK, BLK)], abuf.at[slot], asem.at[slot]
        )

    get_dma(0, 0).start()
    get_aux(0, 0).start()

    lane = jax.lax.broadcasted_iota(jnp.int32, (1, BLK), 1)

    def body(j, _):
        slot = jax.lax.rem(j, 2)

        @pl.when(j + 1 < nblk)
        def _():
            get_dma(1 - slot, j + 1).start()
            get_aux(1 - slot, j + 1).start()

        get_dma(slot, j).wait()
        get_aux(slot, j).wait()
        blk = buf_ref.at[slot]
        band = blk[band0 : band0 + bandn, :]
        aux = abuf[slot]

        # ---- channel update (single-class contract: multiclass runs
        # update_multi_and_hists instead)
        scores = pltpu.bitcast(band[SCORE_ : SCORE_ + 1, :], jnp.float32)
        if n_delta:
            scores = scores + aux[0:1, :]
        label = pltpu.bitcast(band[LABEL_ : LABEL_ + 1, :], jnp.float32)
        weight = (
            pltpu.bitcast(band[WEIGHT_ : WEIGHT_ + 1, :], jnp.float32)
            if use_weight else None
        )
        gv, hv = grad_fn(scores, label, weight)
        gv = gv.astype(jnp.float32)
        hv = hv.astype(jnp.float32)
        if use_mul:
            # GOSS: sampled-rest rows carry the (n-top_k)/other_k
            # gradient up-weighting (goss.hpp:112-117) — scales g/h but
            # NOT the select row, so histogram counts stay row counts
            mulv = aux[6:7, :]
            gv = gv * mulv
            hv = hv * mulv
        if use_sel:
            selv = aux[7:8, :]
        else:
            selv = pltpu.bitcast(band[SEL_ : SEL_ + 1, :], jnp.float32)
        out = band
        out = _setrow(out, G_, pltpu.bitcast(gv, jnp.int32))
        out = _setrow(out, H_, pltpu.bitcast(hv, jnp.int32))
        if use_sel:
            out = _setrow(out, SEL_, pltpu.bitcast(selv, jnp.int32))
        if n_delta:
            out = _setrow(out, SCORE_, pltpu.bitcast(scores, jnp.int32))
        _stream_flush(stage, wsem, _band_block(p_any, band0, bandn, j * BLK), out, j)

        # ---- root histogram from the fresh values (skipped entirely for
        # histogram-free passes — GOSS's gradient-prep pass used to pay
        # the full F*B one-hot/matmul accumulation only to discard it)
        if with_hist:
            pos = lane + j * BLK
            valid = (pos < n).astype(jnp.float32)
            sel = selv * valid
            g = gv * sel
            h = hv * sel
            vals = jnp.concatenate(
                _split3(g) + _split3(h) + [sel.astype(jnp.bfloat16)], axis=0
            )
            _hist_accumulate(blk, vals, acc_ref, nf=nf, nb=nb, bits=bits,
                             max_tile_bytes=_UPDATE_TILE_BYTES)
        return 0

    jax.lax.fori_loop(0, nblk, body, 0)
    _stream_drain(stage, wsem, nblk)
    if with_hist:
        o_ref[:, :] = acc_ref[:, :]
    else:
        o_ref[:, :] = jnp.zeros_like(o_ref)


def update_and_root_hist(p, layout: PLayout, grad_fn, delta=None, sel=None,
                         mul=None, *, num_rows, num_features, num_bins,
                         bits=8, with_hist: bool = True,
                         interpret: bool = False):
    """Fused per-iteration channel maintenance + root histogram: ONE
    streaming pass writes score += delta, fresh (g, h), bagging select —
    in place via input_output_aliases — and returns the root (F, B, 3)
    histogram of the fresh values (the fused trainer starts every tree
    with exactly this pair).  GBDT::Boosting + Bagging + the root
    ConstructHistogram in one pass (gbdt.cpp:692-700, 275-334).

    ``with_hist=False`` runs the identical channel update (bit-for-bit
    the same matrix writes) with the histogram accumulation compiled
    out and returns (p, None) — the GOSS gradient-prep pass, which used
    to pay the full F*B one-hot/matmul work only to discard it."""
    ntot = p.shape[1]
    c = p.shape[0]
    fb = hist_lanes(num_features, num_bins)

    def fit(v):
        v = jnp.asarray(v, jnp.float32)
        pad = ntot - v.shape[0]
        return jnp.concatenate([v, jnp.zeros((pad,), jnp.float32)]) if pad else v

    zero = jnp.zeros((ntot,), jnp.float32)
    use_sel = sel is not None
    # aux rows 0..K-1: pending per-class score deltas; row 7: bagging
    # select.  K <= 7 is enforced by the trainer's eligibility gate.
    if delta is None:
        n_delta = 0
        drows = []
    else:
        delta = jnp.asarray(delta, jnp.float32)
        if delta.ndim > 1:
            delta = delta[0]
        n_delta = 1
        drows = [fit(delta)]
    use_mul = mul is not None
    rows8 = (drows + [zero] * (6 - len(drows))
             + [fit(mul) if use_mul else zero]
             + [fit(sel) if use_sel else zero])
    aux = jnp.stack(rows8)
    lay_rows = (layout.G, layout.H, layout.SEL, layout.SCORE, layout.LABEL,
                layout.ROWID, layout.WEIGHT)
    kern = functools.partial(
        _upd_hist_kernel, nf=num_features, nb=num_bins, band0=layout.WPAD, bits=bits, grad_fn=grad_fn, lay_rows=lay_rows,
        use_sel=use_sel, use_mul=use_mul, use_weight=layout.with_weight,
        n_delta=n_delta, with_hist=with_hist,
    )
    p, out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(1,),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),  # aux
                pl.BlockSpec(memory_space=pl.ANY),  # P (alias)
            ],
            out_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pltpu.VMEM),
            ],
            scratch_shapes=[
                pltpu.VMEM((8, fb), jnp.float32),
                pltpu.VMEM((2, c, BLK), jnp.int32),
                pltpu.VMEM((2, 8, BLK), jnp.float32),
                pltpu.VMEM((2, layout.BAND, BLK), jnp.int32),  # write stage
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=(
            jax.ShapeDtypeStruct(p.shape, jnp.int32),
            jax.ShapeDtypeStruct((8, fb), jnp.float32),
        ),
        input_output_aliases={2: 0},
        compiler_params=_vmem_params(_tile_bytes(c, 2), 2 * 8 * fb * 4),
        interpret=interpret,
        name="update_and_root_hist",
    )(jnp.stack([jnp.int32(num_rows)]), aux, p)
    if not with_hist:
        return p, None
    return p, _hist_from_rows(out, num_features, num_bins)


# ======================================================================
# update_multi_and_hists: K gradient planes + K root histograms, one pass
# ======================================================================
def _upd_multi_kernel(sref, aux_any, p_any_in, p_any, o_ref, acc_ref, buf_ref, abuf,
                      stage, rsem, asem, wsem, *, nf, nb, bits,
                      grad_all_fn, lay, use_sel):
    """One streaming pass over ALL rows: (g_k, h_k) for EVERY class k from
    the score-channel snapshot (GBDT::Boosting computes all K gradient
    planes once per iteration, gbdt.cpp:692-700), bagging select, the
    block's band written back in place, and ALL K root histograms
    accumulated — the K value groups just widen the MXU operand (6K+1
    sublanes)."""
    n = sref[0]
    K = lay.num_score
    band0, bandn = lay.WPAD, lay.BAND
    nblk = (n + BLK - 1) // BLK
    acc_ref[:, :] = jnp.zeros_like(acc_ref)

    def get_dma(slot, j):
        return pltpu.make_async_copy(
            p_any.at[:, pl.ds(j * BLK, BLK)], buf_ref.at[slot], rsem.at[slot]
        )

    def get_aux(slot, j):
        return pltpu.make_async_copy(
            aux_any.at[:, pl.ds(j * BLK, BLK)], abuf.at[slot], asem.at[slot]
        )

    get_dma(0, 0).start()
    get_aux(0, 0).start()

    lane = jax.lax.broadcasted_iota(jnp.int32, (1, BLK), 1)

    def brow(band, r, k=1):
        return band[r - band0 : r - band0 + k, :]

    def body(j, _):
        slot = jax.lax.rem(j, 2)

        @pl.when(j + 1 < nblk)
        def _():
            get_dma(1 - slot, j + 1).start()
            get_aux(1 - slot, j + 1).start()

        get_dma(slot, j).wait()
        get_aux(slot, j).wait()
        blk = buf_ref.at[slot]
        band = blk[band0 : band0 + bandn, :]
        aux = abuf[slot]

        scores = pltpu.bitcast(brow(band, lay.SCORE, K), jnp.float32)
        label = pltpu.bitcast(brow(band, lay.LABEL), jnp.float32)
        weight = (
            pltpu.bitcast(brow(band, lay.WEIGHT), jnp.float32)
            if lay.with_weight else None
        )
        gv, hv = grad_all_fn(scores, label, weight)  # (K, BLK) each
        gv = gv.astype(jnp.float32)
        hv = hv.astype(jnp.float32)
        if use_sel:
            selv = aux[7:8, :]
        else:
            selv = pltpu.bitcast(brow(band, lay.SEL), jnp.float32)
        out = band
        for k in range(K):
            out = _setrow(out, lay.g_row(k) - band0, pltpu.bitcast(gv[k : k + 1], jnp.int32))
            out = _setrow(out, lay.h_row(k) - band0, pltpu.bitcast(hv[k : k + 1], jnp.int32))
        if use_sel:
            out = _setrow(out, lay.SEL - band0, pltpu.bitcast(selv, jnp.int32))
        _stream_flush(stage, wsem, _band_block(p_any, band0, bandn, j * BLK), out, j)

        # ---- K root histograms from the fresh values
        pos = lane + j * BLK
        valid = (pos < n).astype(jnp.float32)
        sel = selv * valid
        groups = []
        for k in range(K):
            groups += _split3(gv[k : k + 1] * sel) + _split3(hv[k : k + 1] * sel)
        groups.append(sel.astype(jnp.bfloat16))
        vals = jnp.concatenate(groups, axis=0)  # (6K + 1, BLK)
        _hist_accumulate(blk, vals, acc_ref, nf=nf, nb=nb, bits=bits,
                         max_tile_bytes=_UPDATE_TILE_BYTES)
        return 0

    jax.lax.fori_loop(0, nblk, body, 0)
    _stream_drain(stage, wsem, nblk)
    o_ref[:, :] = acc_ref[:, :]


def update_multi_and_hists(p, layout: PLayout, grad_all_fn, sel=None,
                           *, num_rows, num_features, num_bins, bits=8,
                           interpret: bool = False):
    """Multiclass per-iteration channel maintenance: ALL K (g, h) planes
    written from the same score snapshot + K root histograms, one
    streaming pass.  Returns (p', [hist_k (F, B, 3) for k in range(K)])."""
    K = layout.num_score
    ntot = p.shape[1]
    c = p.shape[0]
    fb = hist_lanes(num_features, num_bins)
    nv = 6 * K + 1
    nvpad = -(-nv // 8) * 8

    def fit(v):
        v = jnp.asarray(v, jnp.float32)
        pad = ntot - v.shape[0]
        return jnp.concatenate([v, jnp.zeros((pad,), jnp.float32)]) if pad else v

    zero = jnp.zeros((ntot,), jnp.float32)
    use_sel = sel is not None
    aux = jnp.stack([zero] * 7 + [fit(sel) if use_sel else zero])
    kern = functools.partial(
        _upd_multi_kernel, nf=num_features, nb=num_bins,
        bits=bits, grad_all_fn=grad_all_fn, lay=layout, use_sel=use_sel,
    )
    p, out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(1,),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pltpu.VMEM),
            ],
            scratch_shapes=[
                pltpu.VMEM((nvpad, fb), jnp.float32),
                pltpu.VMEM((2, c, BLK), jnp.int32),
                pltpu.VMEM((2, 8, BLK), jnp.float32),
                pltpu.VMEM((2, layout.BAND, BLK), jnp.int32),  # write stage
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=(
            jax.ShapeDtypeStruct(p.shape, jnp.int32),
            jax.ShapeDtypeStruct((nvpad, fb), jnp.float32),
        ),
        input_output_aliases={2: 0},
        compiler_params=_vmem_params(_tile_bytes(c, 2), 2 * nvpad * fb * 4),
        interpret=interpret,
        name="update_multi_and_hists",
    )(jnp.stack([jnp.int32(num_rows)]), aux, p)
    cnt = out[6 * K]
    hists = []
    for k in range(K):
        g = out[6 * k + 0] + (out[6 * k + 1] + out[6 * k + 2])
        h = out[6 * k + 3] + (out[6 * k + 4] + out[6 * k + 5])
        hists.append(_hist_cells(g, h, cnt, num_features, num_bins))
    return p, hists


# ======================================================================
# score_add: in-place score-row segment update (multiclass per-tree,
# chunk-end settle)
# ======================================================================
def _score_band_kernel(aux_any, p_in, p_any, buf, abuf, rsem, asem, wsem, *,
                       band0, bandn, nblk, score_off):
    """Band-streaming score update: score += delta touching ONLY the
    8-aligned mutable band (``update_channels``' ring pattern).  The old
    kernel streamed every matrix row — including the packed bin words —
    just to rewrite them unchanged; reading the band alone halves (or
    better) the traffic of every score-only pass and leaves the bin/rowid
    rows genuinely untouched ("read once per round")."""
    R, K = _URING, _UAHEAD

    def rd(j):
        sl = jax.lax.rem(j, R)
        return pltpu.make_async_copy(
            p_any.at[band0 : band0 + bandn, pl.ds(j * BLK, BLK)], buf.at[sl], rsem.at[sl]
        )

    def rda(j):
        sl = jax.lax.rem(j, R)
        return pltpu.make_async_copy(
            aux_any.at[:, pl.ds(j * BLK, BLK)], abuf.at[sl], asem.at[sl]
        )

    def wr(j):
        sl = jax.lax.rem(j, R)
        return pltpu.make_async_copy(
            buf.at[sl], p_any.at[band0 : band0 + bandn, pl.ds(j * BLK, BLK)], wsem.at[sl]
        )

    for k in range(min(K, nblk)):
        rd(k).start()
        rda(k).start()

    def body(j, _):
        sl = jax.lax.rem(j, R)
        rd(j).wait()
        rda(j).wait()
        blk = buf[sl]
        sc = pltpu.bitcast(blk[score_off : score_off + 1, :], jnp.float32)
        sc = sc + abuf[sl][0:1, :]
        buf[sl] = _setrow(blk, score_off, pltpu.bitcast(sc, jnp.int32))
        wr(j).start()

        @pl.when(j + K < nblk)
        def _():
            @pl.when(j + K - R >= 0)
            def _():
                wr(j + K - R).wait()

            rd(j + K).start()
            rda(j + K).start()

        return 0

    jax.lax.fori_loop(0, nblk, body, 0)
    for k in range(min(_URING, nblk)):
        wr(nblk - 1 - k).wait()


@functools.partial(jax.jit, static_argnames=("layout", "k", "num_rows", "interpret"),
                   donate_argnums=(0,))
def score_add(p, layout: PLayout, delta, k: int = 0, *, num_rows,
              interpret: bool = False):
    """score channel k += delta (N,) in place — the per-tree score update
    of the multiclass fused loop (applied IMMEDIATELY after each tree,
    while the delta's row layout is still current) and the chunk-end
    pending-delta settle.  Streams only the mutable band, not the full
    matrix; donated at the jit level so standalone calls never pay a
    defensive whole-matrix copy."""
    ntot = p.shape[1]
    v = jnp.asarray(delta, jnp.float32)
    pad = ntot - v.shape[0]
    if pad:
        v = jnp.concatenate([v, jnp.zeros((pad,), jnp.float32)])
    aux = jnp.concatenate([v[None, :], jnp.zeros((7, ntot), jnp.float32)], axis=0)
    nblk = (int(num_rows) + BLK - 1) // BLK
    band0, bandn = layout.WPAD, layout.BAND
    kern = functools.partial(
        _score_band_kernel, band0=band0, bandn=bandn, nblk=nblk,
        score_off=layout.SCORE + k - band0,
    )
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0,
            grid=(1,),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),  # aux
                pl.BlockSpec(memory_space=pl.ANY),  # P (alias)
            ],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                pltpu.VMEM((_URING, bandn, BLK), jnp.int32),
                pltpu.VMEM((_URING, 8, BLK), jnp.float32),
                pltpu.SemaphoreType.DMA((_URING,)),
                pltpu.SemaphoreType.DMA((_URING,)),
                pltpu.SemaphoreType.DMA((_URING,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(p.shape, jnp.int32),
        input_output_aliases={1: 0},
        interpret=interpret,
        name="score_add",
    )(aux, p)


# ======================================================================
# split_stream: two-ended in-place partition + both-children histograms
# ======================================================================
def _band_block(p_any, band0, bandn, off):
    """The band rows of the BLK columns at ``off``: what a channel update
    writes back (band0 and bandn are multiples of the 8-row tile)."""
    return p_any.at[band0 : band0 + bandn, pl.ds(off, BLK)]


def _stage_wait(stage, wsem, nstart, flushing=True):
    """Before write ``nstart`` refills its stage slot: wait for the write
    that used the slot two starts ago."""
    slot = jax.lax.rem(nstart, 2)

    @pl.when(flushing & (nstart >= 2))
    def _():
        pltpu.make_async_copy(stage.at[slot], stage.at[slot], wsem.at[slot]).wait()


def _stage_start(stage, wsem, dst, nstart):
    slot = jax.lax.rem(nstart, 2)
    pltpu.make_async_copy(stage.at[slot], dst, wsem.at[slot]).start()


def _stream_flush(stage, wsem, dst, merged, nstart):
    """Start one aligned write of ``merged`` to the HBM block ``dst`` via
    the double-buffered stage."""
    _stage_wait(stage, wsem, nstart)
    stage[jax.lax.rem(nstart, 2)] = merged
    _stage_start(stage, wsem, dst, nstart)


def _stream_drain(stage, wsem, nstarts):
    @pl.when(nstarts >= 1)
    def _():
        pltpu.make_async_copy(stage.at[0], stage.at[0], wsem.at[0]).wait()

    @pl.when(nstarts >= 2)
    def _():
        pltpu.make_async_copy(stage.at[1], stage.at[1], wsem.at[1]).wait()


def _run_segment(
    p_any, hist_ref, scalars,
    buf, carL, carR, stageL, stageR, tri_ref, oh_ref, pacc,
    rsemF, rsemB, csemL, csemR, wsemL, wsemR,
    *, c, bits, nf, nb, rows,
):
    """One pass over one parent segment: stable-unordered in-place
    partition by the split predicate + the histogram rows of BOTH
    children accumulated into ``hist_ref`` (caller zeroes it and builds
    ``tri_ref`` once).  Returns the left-child row count.

    Two-ended block protocol (verified by exhaustive simulation in
    tests/test_pgrow.py::test_twoend_protocol): blocks are read from the
    front and the back of the segment; lefts compact forward into
    front-vacated space, rights compact backward into back-vacated space.
    Before classifying, any side whose vacated space hit zero is topped
    up with a demand read; a flush whose target block is the other side's
    in-flight read waits that read first.  Invariants guarantee writes
    only ever land on blocks already read.

    Past a few hundred columns a (C, BLK) block is too large to hold as
    one value, so the block stays in its VMEM buffer and is worked on in
    groups of channel rows: the left/right decision reads the one word row
    that holds the split column, the histograms walk the bin words group
    by group (``_hist_accumulate``), and the permutation the decision
    implies (``_staircase``: its one-hots, once a block) is applied to
    ``_PERM_GROUP_ROWS`` rows at a time (``_apply_staircase`` under
    ``_for_row_groups``), each group merged into the carries and the
    write stages before the next is loaded.  ``buf`` is the two read
    rings in one array (front slots 0.._RING-1, back slots _RING..) so
    the hand block is one dynamic slot of it."""
    (start, cnt, word, shift, zero_bin, dbz, thr, is_cat,
     off_lo, off_hi, bias) = scalars
    # EFB bundle range remap (feature_group.h PushData layout): the
    # feature's bins occupy stored values [off_lo, off_hi) with ``bias``
    # correcting a dropped zero default bin; values outside the range
    # mean "this feature at its default".  Unbundled features pass
    # (0, 1<<bits, 0), making fb == raw value.
    g_row, h_row, sel_row = rows

    base = pl.multiple_of((start // BLK) * BLK, _LANE)
    head = start - base
    E = head + cnt
    nblk = (E + BLK - 1) // BLK

    # preload carries: carL holds the head block (lanes < head preserved
    # as pre-filled carry), carR the tail block (lanes >= E-(nblk-1)*BLK
    # preserved, filled from the end)
    cpL = pltpu.make_async_copy(p_any.at[:, pl.ds(base, BLK)], carL, csemL)
    # clamp: an empty block-aligned segment (cnt=0, head=0 -> nblk=0)
    # would otherwise issue a DMA at base-BLK (negative when base=0);
    # the preloaded data is unused in that case
    cpR = pltpu.make_async_copy(
        p_any.at[:, pl.ds(base + jnp.maximum(nblk - 1, 0) * BLK, BLK)], carR, csemR
    )
    cpL.start()
    cpR.start()
    cpL.wait()
    cpR.wait()

    def dmaF(k):  # k-th front read = block k
        slot = jax.lax.rem(k, _RING)
        return pltpu.make_async_copy(
            p_any.at[:, pl.ds(base + k * BLK, BLK)], buf.at[slot], rsemF.at[slot]
        )

    def dmaB(k):  # k-th back read = block nblk-1-k
        slot = jax.lax.rem(k, _RING)
        return pltpu.make_async_copy(
            p_any.at[:, pl.ds(base + (nblk - 1 - k) * BLK, BLK)],
            buf.at[_RING + slot],
            rsemB.at[slot],
        )

    lane = jax.lax.broadcasted_iota(jnp.int32, (1, BLK), 1)
    iota_8 = jax.lax.broadcasted_iota(jnp.int32, (8, 1), 0)
    vmask = (1 << bits) - 1

    def body(j, st):
        if_, ib, cf, cb, kf, kb, fl, fr, cl, cr = st

        # ---- demand reads: top up any side whose vacated space is 0
        budget = if_ + ib < nblk
        doF = ((cf - fl) == 0) & ((if_ > cf) | budget)
        issF = doF & (if_ == cf)

        @pl.when(issF)
        def _():
            dmaF(if_).start()

        if_ = if_ + issF

        @pl.when(doF)
        def _():
            dmaF(cf).wait()

        cf = cf + doF

        budget = if_ + ib < nblk
        doB = ((cb - fr) == 0) & ((ib > cb) | budget)
        issB = doB & (ib == cb)

        @pl.when(issB)
        def _():
            dmaB(ib).start()

        ib = ib + issB

        @pl.when(doB)
        def _():
            dmaB(cb).wait()

        cb = cb + doB

        # ---- force-consume so a hand block exists
        budget = if_ + ib < nblk
        noq = ((cf - kf) == 0) & ((cb - kb) == 0)
        availF = (if_ > cf) | budget
        doCF = noq & availF
        issCF = doCF & (if_ == cf)

        @pl.when(issCF)
        def _():
            dmaF(if_).start()

        if_ = if_ + issCF

        @pl.when(doCF)
        def _():
            dmaF(cf).wait()

        cf = cf + doCF
        doCB = noq & (~availF)
        issCB = doCB & (ib == cb)

        @pl.when(issCB)
        def _():
            dmaB(ib).start()

        ib = ib + issCB

        @pl.when(doCB)
        def _():
            dmaB(cb).wait()

        cb = cb + doCB

        # ---- hand block: it stays in its ring slot
        useF = (cf - kf) > 0
        hand = buf.at[jnp.where(useF, jax.lax.rem(kf, _RING),
                                _RING + jax.lax.rem(kb, _RING))]
        jh = jnp.where(useF, kf, nblk - 1 - kb)
        kf = kf + useF
        kb = kb + (~useF)

        # ---- classify: split predicate (DataPartition::Split fused with
        # the DefaultValueForZero bin remap of dense_bin.hpp:191-232) on
        # the split column's word row, picked out of its 8-row tile
        pos = lane + jh * BLK
        valid = (pos >= head) & (pos < E)
        w8 = pl.multiple_of((word // 8) * 8, 8)
        wordrow = jnp.sum(jnp.where(iota_8 == word - w8, hand[pl.ds(w8, 8), :], 0),
                          axis=0, keepdims=True)
        binv = (wordrow >> shift) & vmask
        in_range = (binv >= off_lo) & (binv < off_hi)
        fb = jnp.where(in_range, binv - off_lo + bias, zero_bin)
        fv = jnp.where(fb == zero_bin, dbz, fb)
        eqv = (fv == thr).astype(jnp.int32)
        lev = (fv <= thr).astype(jnp.int32)
        # select on int32 (Mosaic cannot legalize arith.select on i1 vectors)
        gl = (jnp.where(is_cat == 1, eqv, lev) == 1) & valid
        gr = valid & (~gl)
        glm = gl.astype(jnp.float32)
        grm = gr.astype(jnp.float32)

        # ---- both-children histograms while the block is in VMEM: the
        # bin one-hots (the VPU-bound part) are shared; the value rows
        # just widen 7 -> 14 sublanes (free on the MXU)
        selv = pltpu.bitcast(hand[sel_row : sel_row + 1, :], jnp.float32)
        gv = pltpu.bitcast(hand[g_row : g_row + 1, :], jnp.float32) * selv
        hv = pltpu.bitcast(hand[h_row : h_row + 1, :], jnp.float32) * selv
        vals = jnp.concatenate(
            _split3(gv * glm) + _split3(hv * glm) + [(selv * glm).astype(jnp.bfloat16)]
            + _split3(gv * grm) + _split3(hv * grm) + [(selv * grm).astype(jnp.bfloat16)],
            axis=0,
        )  # (14, BLK)
        _hist_accumulate(hand, vals, hist_ref, nf=nf, nb=nb, bits=bits,
                         max_tile_bytes=_SPLIT_TILE_BYTES)

        # ---- in-block compaction: where each lane goes, once for the block
        cntl, cntr, win = _staircase(glm, grm, cl, cr, tri_ref, oh_ref)

        # ---- left flush (forward, into front-vacated space)
        tL = cl + cntl
        flushL = tL >= BLK
        # if the target block is an in-flight read, consume it first
        nwB = flushL & (ib > cb) & (fl == nblk - 1 - cb)

        @pl.when(nwB)
        def _():
            dmaB(cb).wait()

        cb = cb + nwB
        nwF = flushL & (if_ > cf) & (fl == cf)

        @pl.when(nwF)
        def _():
            dmaF(cf).wait()

        cf = cf + nwF

        # ---- right flush (backward, into back-vacated space)
        tR = cr + cntr
        flushR = tR >= BLK
        rtgt = nblk - 1 - fr
        nwB2 = flushR & (ib > cb) & (rtgt == nblk - 1 - cb)

        @pl.when(nwB2)
        def _():
            dmaB(cb).wait()

        cb = cb + nwB2
        nwF2 = flushR & (if_ > cf) & (rtgt == cf)

        @pl.when(nwF2)
        def _():
            dmaF(cf).wait()

        cf = cf + nwF2

        # ---- the permutation itself, a group of channel rows at a time:
        # the four byte planes of the group's rows through the two
        # one-hots, merged under the carries; a side that fills a block
        # stages it for its write, and its carry keeps the overflow
        _stage_wait(stageL, wsemL, fl, flushL)
        _stage_wait(stageR, wsemR, fr, flushR)
        slotL = jax.lax.rem(fl, 2)
        slotR = jax.lax.rem(fr, 2)

        def permute(rws):
            permL, permR = _apply_staircase(hand[rws, :], oh_ref, pacc, win)
            mergedL = jnp.where(lane < cl, carL[rws, :], permL)
            mergedR = jnp.where(lane >= BLK - cr, carR[rws, :], permR)

            @pl.when(flushL)
            def _():
                stageL[slotL, rws, :] = mergedL

            @pl.when(flushR)
            def _():
                stageR[slotR, rws, :] = mergedR

            carL[rws, :] = jnp.where(flushL, permL, mergedL)
            carR[rws, :] = jnp.where(flushR, permR, mergedR)

        _for_row_groups(c, permute)

        @pl.when(flushL)
        def _():
            _stage_start(stageL, wsemL, p_any.at[:, pl.ds(base + fl * BLK, BLK)], fl)

        @pl.when(flushR)
        def _():
            _stage_start(stageR, wsemR, p_any.at[:, pl.ds(base + rtgt * BLK, BLK)], fr)

        cl = jnp.where(flushL, tL - BLK, tL)
        fl = fl + flushL
        cr = jnp.where(flushR, tR - BLK, tR)
        fr = fr + flushR

        # ---- prefetch the hand side
        budget = if_ + ib < nblk
        pfF = budget & useF & ((if_ - kf) < _RING)

        @pl.when(pfF)
        def _():
            dmaF(if_).start()

        if_ = if_ + pfF
        budget = if_ + ib < nblk
        pfB = budget & (~useF) & ((ib - kb) < _RING)

        @pl.when(pfB)
        def _():
            dmaB(ib).start()

        ib = ib + pfB
        return (if_, ib, cf, cb, kf, kb, fl, fr, cl, cr)

    z = jnp.int32(0)
    st = jax.lax.fori_loop(
        0, nblk, body,
        (z, z, z, z, z, z, z, z, jnp.int32(head), nblk * BLK - E),
    )
    if_, ib, cf, cb, kf, kb, fl, fr, cl, cr = st

    # the final carries exactly tile one block (cl + cr ∈ {0, BLK}):
    # lefts at [0, cl), rights at [cl, BLK) == [BLK-cr, BLK)
    has_mid = (cl + cr) > 0

    @pl.when(has_mid)
    def _():
        _stage_wait(stageL, wsemL, fl)
        slot = jax.lax.rem(fl, 2)

        def merge(rws):
            stageL[slot, rws, :] = jnp.where(lane < cl, carL[rws, :], carR[rws, :])

        _for_row_groups(c, merge)
        _stage_start(stageL, wsemL, p_any.at[:, pl.ds(base + fl * BLK, BLK)], fl)

    _stream_drain(stageL, wsemL, fl + has_mid)
    _stream_drain(stageR, wsemR, fr)

    # drain any still-in-flight reads (their data is unused)
    @pl.when(if_ > cf)
    def _():
        dmaF(cf).wait()

    @pl.when(ib > cb)
    def _():
        dmaB(cb).wait()

    return fl * BLK + cl - head


def _compaction_scratch(c: int) -> list:
    """VMEM of the in-block compaction (``_staircase``, ``_apply_staircase``)."""
    return [
        pltpu.VMEM((_LANE, _LANE), jnp.bfloat16),  # tri
        pltpu.VMEM((2 * _TILES, 2 * _LANE, _LANE), jnp.bfloat16),  # oh: a block's one-hots
        # pacc: the rows _for_row_groups hands over at a time, permuted
        pltpu.VMEM((2, min(c, _PERM_GROUP_ROWS), BLK + _LANE), jnp.int32),
    ]


def _partition_bytes(c: int) -> int:
    """VMEM bytes of ``_partition_scratch(c)``."""
    return sum(int(np.prod(v.shape)) * jnp.dtype(v.dtype).itemsize
               for v in _partition_scratch(c))


def level_stream_vmem_bytes(num_cols: int, num_bins: int, num_score: int = 1,
                            bits: int = 8) -> int:
    """VMEM scratch of the largest streaming kernel at this width: what
    ``eligible`` holds against the chip's budget."""
    c = PLayout(num_cols, num_score=num_score, bits=bits).C
    return _partition_bytes(c) + 2 * 16 * hist_lanes(num_cols, num_bins) * 4


def _partition_scratch(c: int) -> list:
    """VMEM the two-ended partition holds whatever the histogram's size."""
    return [
        pltpu.VMEM((2 * _RING, c, BLK), jnp.int32),  # read rings: front, back
        pltpu.VMEM((c, BLK), jnp.int32),  # carL
        pltpu.VMEM((c, BLK), jnp.int32),  # carR
        pltpu.VMEM((2, c, BLK), jnp.int32),  # stageL
        pltpu.VMEM((2, c, BLK), jnp.int32),  # stageR
    ] + _compaction_scratch(c)


def perm_tiles() -> int:
    """128 x 128 one-hot tiles the MXU is handed for one block's compaction:
    the triangle once (all ``2 * _TILES`` lane tiles' flags stream through it
    stacked on sublanes) and a two-tile window for each source tile and side.
    The dense form this replaced took ``_TILES**2`` for the count and
    ``2 * _TILES**2`` for the permutation."""
    return 1 + 2 * _TILES * 2


def _build_tri(tri_ref):
    """Triangular operand of the in-tile running count, built once per
    kernel: 16 K cells (cheaper than reading it from HBM every launch)."""
    ii = jax.lax.broadcasted_iota(jnp.int32, (_LANE, _LANE), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (_LANE, _LANE), 1)
    tri_ref[:, :] = (ii <= jj).astype(jnp.bfloat16)


def _staircase(glm, grm, cl, cr, tri_ref, oh_ref):
    """Where a block's lanes go, as the one-hots that take them there.

    ``glm`` / ``grm`` are the (1, BLK) 0/1 flags of the lanes that go left /
    right, ``cl`` / ``cr`` the fills of the two carries.  The k-th left lane
    of the block lands on lane ``cl + k`` and the k-th right lane on lane
    ``BLK - cr - 1 - k``, both mod BLK: a stable compaction, so the targets
    of ONE source lane tile are at most ``_LANE`` consecutive lanes, which
    lie in two consecutive target tiles.  For each source tile ``s`` and
    side, ``oh_ref[side * _TILES + s]`` gets the (2 * _LANE, _LANE) one-hot
    [window lane, source lane] of that two-tile window.

    The running count is in-tile: the 2 * _TILES flag tiles stacked on
    sublanes against one (_LANE, _LANE) triangle; the tile totals leave the
    vector unit as scalars and their prefix is scalar arithmetic, which the
    window offsets have to be anyway (they address ``pacc``).

    Returns (left count, right count, the ``2 * _TILES`` window offsets in
    lanes, ``oh_ref``'s order; multiples of _LANE below BLK)."""
    flags = jnp.concatenate(
        [m[:, s * _LANE:(s + 1) * _LANE] for m in (glm, grm) for s in range(_TILES)],
        axis=0)  # (2 * _TILES, _LANE)
    incl = jax.lax.dot_general(
        flags.astype(jnp.bfloat16), tri_ref[:, :], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(jnp.int32)
    iota_w = jax.lax.broadcasted_iota(jnp.int32, (2 * _LANE, _LANE), 0)
    # first target lane of the tile's window, kept non-negative: lefts count
    # up from cl, rights down from BLK - cr - 1 (so a tile's LAST right lane
    # is its window's first)
    base = [cl, 2 * BLK - cr - _LANE]
    win = []
    for side in range(2):
        b = base[side]
        for s in range(_TILES):
            k = side * _TILES + s
            inc = incl[k:k + 1, :]
            lane0 = b & (_LANE - 1)
            rel = inc + (lane0 - 1) if side == 0 else (lane0 + _LANE) - inc
            rel = jnp.where(flags[k:k + 1, :] > 0, rel, -1)
            oh_ref[k] = (iota_w == rel).astype(jnp.bfloat16)
            win.append(b & (BLK - _LANE))  # the tile b lies in, mod BLK
            tot = incl[k, _LANE - 1]
            b = b + tot if side == 0 else b - tot
        base[side] = b
    return base[0] - cl, (2 * BLK - cr - _LANE) - base[1], win


def _apply_staircase(rows_i32, oh_ref, pacc, win):
    """The block's compaction applied to a group of channel rows: (rows, BLK)
    int32 -> the same rows with the left lanes compacted (``permL``) and with
    the right lanes compacted (``permR``), zero where no lane lands.  Each
    source lane tile's four byte planes go through its two-tile one-hot
    (integers 0..255 are exact in bf16) into ``pacc`` at the window's
    offset; a window that starts in the last tile spills into a ninth, which
    is folded back onto the first.  Every target lane receives from exactly
    one source lane, so OR-ing the pieces is exact."""
    planes = _planes(rows_i32)
    nrow = rows_i32.shape[0]
    pacc[:, 0:nrow, :] = jnp.zeros((2, nrow, BLK + _LANE), jnp.int32)
    for s in range(_TILES):
        src = planes[:, s * _LANE:(s + 1) * _LANE]
        for side in range(2):
            k = side * _TILES + s
            piece = _unplanes(
                jax.lax.dot_general(src, oh_ref[k], (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32), nrow)
            lanes = pl.ds(pl.multiple_of(win[k], _LANE), 2 * _LANE)
            pacc[side, 0:nrow, lanes] |= piece
    pacc[:, 0:nrow, 0:_LANE] |= pacc[:, 0:nrow, BLK:BLK + _LANE]
    return pacc[0, 0:nrow, 0:BLK], pacc[1, 0:nrow, 0:BLK]


def _split_kernel(
    sref, p_in, p_any, hist_ref, nl_ref,
    buf, carL, carR, stageL, stageR, tri_ref, oh_ref, pacc,
    rsemF, rsemB, csemL, csemR, wsemL, wsemR,
    *, c, bits, nf, nb, rows,
):
    """Single-segment wrapper over _run_segment (the classic per-split
    launch; grow_tree_partitioned's deep tail and standalone callers)."""
    _build_tri(tri_ref)
    hist_ref[:, :] = jnp.zeros_like(hist_ref)
    scalars = tuple(sref[k] for k in range(11))
    nl = _run_segment(
        p_any, hist_ref, scalars, buf, carL, carR, stageL, stageR,
        tri_ref, oh_ref, pacc, rsemF, rsemB, csemL, csemR, wsemL, wsemR,
        c=c, bits=bits, nf=nf, nb=nb, rows=rows,
    )
    nl_ref[0] = nl


def _level_kernel(
    sref, p_in, p_any, hist_out, nl_ref,
    buf, carL, carR, stageL, stageR, tri_ref, oh_ref, pacc, hacc,
    rsemF, rsemB, csemL, csemR, wsemL, wsemR, hsem,
    *, c, bits, nf, nb, rows,
):
    """One launch per tree LEVEL: partition EVERY active leaf segment by
    its chosen split and emit both children's histograms per segment —
    the per-split kernel-launch + bookkeeping fixed cost (not measured
    on this machine) collapses to one launch for the whole level.  Segments are disjoint [start, start+cnt)
    ranges processed sequentially with the same two-ended in-place
    protocol (_run_segment); per-segment (16, F*B) histograms are
    DMA'd out double-buffered while the next segment streams.

    sref: (1 + smax, 12) int32 — row 0 holds [n_active, ...]; row 1+s
    holds segment s's [start, cnt, word, shift, zero_bin, dbz, thr,
    is_cat, off_lo, off_hi, bias, 0]."""
    n_active = sref[0, 0]
    _build_tri(tri_ref)

    def one_seg(s, _):
        slot = jax.lax.rem(s, 2)

        # wait for the DMA that used this hist slot two segments ago
        @pl.when(s >= 2)
        def _():
            pltpu.make_async_copy(hacc.at[slot], hacc.at[slot], hsem.at[slot]).wait()

        hacc[slot] = jnp.zeros_like(hacc[slot])
        scalars = tuple(sref[1 + s, k] for k in range(11))
        nl = _run_segment(
            p_any, hacc.at[slot], scalars, buf, carL, carR,
            stageL, stageR, tri_ref, oh_ref, pacc, rsemF, rsemB, csemL, csemR,
            wsemL, wsemR,
            c=c, bits=bits, nf=nf, nb=nb, rows=rows,
        )
        nl_ref[s] = nl
        pltpu.make_async_copy(hacc.at[slot], hist_out.at[s], hsem.at[slot]).start()
        return 0

    jax.lax.fori_loop(0, n_active, one_seg, 0)

    @pl.when(n_active >= 1)
    def _():
        s = n_active - 1
        slot = jax.lax.rem(s, 2)
        pltpu.make_async_copy(hacc.at[slot], hacc.at[slot], hsem.at[slot]).wait()

    @pl.when(n_active >= 2)
    def _():
        s = n_active - 2
        slot = jax.lax.rem(s, 2)
        pltpu.make_async_copy(hacc.at[slot], hacc.at[slot], hsem.at[slot]).wait()


@functools.partial(jax.jit, static_argnames=("num_features", "num_bins", "bits", "rows", "smax", "interpret"),
                   donate_argnums=(0,))
def level_stream(p, seg_tab, n_active, *, num_features, num_bins, bits=8,
                 rows=None, smax, interpret=False):
    """Partition all ``n_active`` leaf segments described by ``seg_tab``
    in place in ONE kernel launch and return every segment's left count
    and both-children histograms.

    seg_tab: (smax, 12) int32 rows [start, cnt, word, shift, zero_bin,
    dbz, thr, is_cat, off_lo, off_hi, bias, 0] (same scalar contract as
    split_stream).  Returns (p', nl (smax,), hists (smax, 16, hist_lanes)) —
    hist rows 0:7 = left child (3-plane g, 3-plane h, count), 7:14 =
    right child; rows for s >= n_active are undefined."""
    if rows is None:
        wpad = -(-num_words(num_features, bits) // 8) * 8
        rows = (wpad, wpad + 1, wpad + 2)
    c = p.shape[0]
    # sliced VMEM refs (hacc.at[slot]) must be lane-tile (128) aligned
    fbp = hist_lanes(num_features, num_bins)
    hdr = jnp.zeros((1, 12), jnp.int32).at[0, 0].set(jnp.int32(n_active))
    sv = jnp.concatenate([hdr, seg_tab.astype(jnp.int32)], axis=0)
    p, hist, nl = pl.pallas_call(
        functools.partial(_level_kernel, c=c, bits=bits, nf=num_features,
                          nb=num_bins, rows=rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],  # P (alias)
            out_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),  # hists (DMA'd per segment)
                pl.BlockSpec(memory_space=pltpu.SMEM),
            ],
            scratch_shapes=_partition_scratch(c) + [
                pltpu.VMEM((2, 16, fbp), jnp.float32),  # hacc (double-buffered)
                pltpu.SemaphoreType.DMA((_RING,)),  # rsemF
                pltpu.SemaphoreType.DMA((_RING,)),  # rsemB
                pltpu.SemaphoreType.DMA(()),  # csemL
                pltpu.SemaphoreType.DMA(()),  # csemR
                pltpu.SemaphoreType.DMA((2,)),  # wsemL
                pltpu.SemaphoreType.DMA((2,)),  # wsemR
                pltpu.SemaphoreType.DMA((2,)),  # hsem
            ],
        ),
        out_shape=(
            jax.ShapeDtypeStruct(p.shape, jnp.int32),
            jax.ShapeDtypeStruct((smax, 16, fbp), jnp.float32),
            jax.ShapeDtypeStruct((smax,), jnp.int32),
        ),
        input_output_aliases={1: 0},
        compiler_params=_vmem_params(_partition_bytes(c), 2 * 16 * fbp * 4),
        interpret=interpret,
        name="level_stream",
    )(sv, p)
    return p, nl, hist


@functools.partial(jax.jit, static_argnames=("num_features", "num_bins", "bits", "rows", "interpret"),
                   donate_argnums=(0,))
def split_stream(p, start, cnt, word, shift, zero_bin, dbz, thr, is_cat,
                 off_lo=0, off_hi=256, bias=0, *, num_features, num_bins,
                 bits=8, rows=None, interpret=False):
    """Partition the leaf segment [start, start+cnt) of ``p`` in place by
    the split predicate AND return both children's histograms from the
    same pass.

    Lefts land at [start, start+nl), rights at [start+nl, start+cnt)
    (order within each child unspecified).  Returns (p', nl,
    ``child_planes``): both children's histograms as six planes of
    ``hist_lanes`` lanes."""
    if rows is None:
        wpad = -(-num_words(num_features, bits) // 8) * 8
        rows = (wpad, wpad + 1, wpad + 2)
    c = p.shape[0]
    fb = hist_lanes(num_features, num_bins)
    sv = jnp.stack(
        [
            jnp.int32(start), jnp.int32(cnt), jnp.int32(word), jnp.int32(shift),
            jnp.int32(zero_bin), jnp.int32(dbz), jnp.int32(thr), jnp.int32(is_cat),
            jnp.int32(off_lo), jnp.int32(off_hi), jnp.int32(bias),
        ]
    )
    p, hist, nl = pl.pallas_call(
        functools.partial(_split_kernel, c=c, bits=bits, nf=num_features,
                          nb=num_bins, rows=rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],  # P (alias)
            out_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pltpu.SMEM),
            ],
            scratch_shapes=_partition_scratch(c) + [
                pltpu.SemaphoreType.DMA((_RING,)),  # rsemF
                pltpu.SemaphoreType.DMA((_RING,)),  # rsemB
                pltpu.SemaphoreType.DMA(()),  # csemL
                pltpu.SemaphoreType.DMA(()),  # csemR
                pltpu.SemaphoreType.DMA((2,)),  # wsemL
                pltpu.SemaphoreType.DMA((2,)),  # wsemR
            ],
        ),
        out_shape=(
            jax.ShapeDtypeStruct(p.shape, jnp.int32),
            jax.ShapeDtypeStruct((16, fb), jnp.float32),
            jax.ShapeDtypeStruct((1,), jnp.int32),
        ),
        input_output_aliases={1: 0},
        compiler_params=_vmem_params(_partition_bytes(c), 16 * fb * 4),
        interpret=interpret,
        name="split_stream",
    )(sv, p)
    return p, nl[0], child_planes(hist)


def child_planes(out):
    """``split_stream``'s kernel rows (a slot's of ``level_stream``) ->
    (6, hist_lanes): the left child's g, h and count planes, then the
    right's, one lane a cell.  What the split search reads (through
    ``plane_cells``) and what the data-parallel tail all-reduces: the
    planes are dense, where a ``(F, B, 3)`` histogram has its 3 on the
    lanes and 125 of every 128 lanes padding.  (Six rows stacked, not
    ``sibling_planes`` reshaped: the compiler moves a reshape through the
    all-reduce, whose operand the compile tests pin as ``f32[6, lanes]``.)"""
    return jnp.stack(_planes_from_rows(out, 0) + _planes_from_rows(out, 7))


# ======================================================================
# update_channels: in-place gradient / bagging / score channel refresh
# ======================================================================
_URING = 8  # ring depth for the band streamer
_UAHEAD = 5  # reads primed ahead; write waits then trail by R-K=3 blocks
#             (deferring each write's wait >=2 blocks keeps it off the
#             critical path; its latency is not measured on this machine)


def _update_kernel(aux_any, p_in, p_any, buf, abuf, rsem, asem, wsem, *,
                   band0, bandn, naux, nblk, grad_fn, score_off, label_off,
                   weight_off, use_weight, use_sel, k_class):
    """Stream the mutable band: score += delta (aux row 0), then
    (g, h) = grad_fn(score, label, weight) written into rows 0..1 of the
    band, select = aux row 1 (bagging) when use_sel.

    The band layout within the streamed window is
      [0]=g [1]=h [2]=sel [3..3+K-1]=scores [3+K]=label [4+K]=rowid
      [5+K]=weight — i.e. rows [band0, band0+bandn) of P.

    One ring of _URING block buffers: block j reads into and writes back
    from slot j%R.  Reads run _UAHEAD blocks ahead; starting read j+K
    first waits write j+K-R (same slot), giving every write R-K blocks
    of slack before anything blocks on it."""
    R, K = _URING, _UAHEAD

    def rd(j):
        sl = jax.lax.rem(j, R)
        return pltpu.make_async_copy(
            p_any.at[band0 : band0 + bandn, pl.ds(j * BLK, BLK)], buf.at[sl], rsem.at[sl]
        )

    def rda(j):
        sl = jax.lax.rem(j, R)
        return pltpu.make_async_copy(
            aux_any.at[:, pl.ds(j * BLK, BLK)], abuf.at[sl], asem.at[sl]
        )

    def wr(j):
        sl = jax.lax.rem(j, R)
        return pltpu.make_async_copy(
            buf.at[sl], p_any.at[band0 : band0 + bandn, pl.ds(j * BLK, BLK)], wsem.at[sl]
        )

    for k in range(min(K, nblk)):
        rd(k).start()
        rda(k).start()

    def body(j, _):
        sl = jax.lax.rem(j, R)
        rd(j).wait()
        rda(j).wait()
        blk = buf[sl]
        aux = abuf[sl]
        delta = aux[0:1, :]
        score = pltpu.bitcast(blk[score_off + k_class : score_off + k_class + 1, :],
                              jnp.float32) + delta
        label = pltpu.bitcast(blk[label_off : label_off + 1, :], jnp.float32)
        if use_weight:
            weight = pltpu.bitcast(blk[weight_off : weight_off + 1, :], jnp.float32)
        else:
            weight = None
        g, h = grad_fn(score, label, weight)
        out = blk
        out = _setrow(out, 0, pltpu.bitcast(g.astype(jnp.float32), jnp.int32))
        out = _setrow(out, 1, pltpu.bitcast(h.astype(jnp.float32), jnp.int32))
        if use_sel:
            out = _setrow(out, 2, pltpu.bitcast(aux[1:2, :], jnp.int32))
        out = _setrow(out, score_off + k_class,
                      pltpu.bitcast(score, jnp.int32))
        buf[sl] = out
        wr(j).start()

        @pl.when(j + K < nblk)
        def _():
            @pl.when(j + K - R >= 0)
            def _():
                wr(j + K - R).wait()

            rd(j + K).start()
            rda(j + K).start()

        return 0

    jax.lax.fori_loop(0, nblk, body, 0)
    # drain: the in-loop wait fires only while reads remain (j+K < nblk),
    # so the last min(R, nblk) writes are still un-waited
    for k in range(min(R, nblk)):
        wr(nblk - 1 - k).wait()


def _setrow(mat, r, row):
    """Replace row ``r`` (static) of (R, BLK) with (1, BLK) ``row``.
    Builds without zero-size slices (Mosaic rejects (0, BLK) vectors)."""
    parts = []
    if r > 0:
        parts.append(mat[:r])
    parts.append(row)
    if r + 1 < mat.shape[0]:
        parts.append(mat[r + 1 :])
    return jnp.concatenate(parts, axis=0) if len(parts) > 1 else row


def update_channels(p, layout: PLayout, grad_fn, delta=None, sel=None,
                    k_class: int = 0, interpret: bool = False):
    """In-place refresh of the mutable band: ``score[k] += delta`` then
    ``g, h = grad_fn(score, label, weight)`` and optionally
    ``select = sel`` — the per-iteration channel maintenance of the fused
    trainer (GBDT::Boosting + Bagging, gbdt.cpp:692-700, 275-334) as ONE
    aliased Pallas pass.

    Exists because ANY XLA-level write to the big matrix (even a
    one-element update on a donated loop carry) costs a pathological
    whole-array copy on this backend; only Pallas input_output_aliases
    mutate in place — see the module docstring for the carry-layout
    contract that keeps the donated matrix XLA-write-free end to end.
    ``delta``/``sel`` are (N,)-or-longer f32 vectors (padded with zeros
    up to p.shape[1] here)."""
    ntot = p.shape[1]
    # floor, not ceil: P has n + BLK columns, so floor(ntot/BLK) blocks
    # always cover every real row without the last window overrunning
    nblk = ntot // BLK
    aux_rows = []
    zero = jnp.zeros((ntot,), jnp.float32)

    def fit(v):
        v = jnp.asarray(v, jnp.float32)
        pad = ntot - v.shape[0]
        return jnp.concatenate([v, jnp.zeros((pad,), jnp.float32)]) if pad else v

    aux_rows.append(fit(delta) if delta is not None else zero)
    use_sel = sel is not None
    aux_rows.append(fit(sel) if use_sel else zero)
    # 8 rows: DMA row-slices must be (8, 128)-tile aligned; rows 2..7 pad
    aux = jnp.concatenate(
        [jnp.stack(aux_rows), jnp.zeros((6, ntot), jnp.float32)], axis=0
    )  # (8, ntot) f32

    band0, bandn = layout.WPAD, layout.BAND
    kern = functools.partial(
        _update_kernel,
        band0=band0, bandn=bandn, naux=2, nblk=nblk, grad_fn=grad_fn,
        score_off=3 + 0, label_off=3 + layout.num_score,
        weight_off=3 + layout.num_score + 2,
        use_weight=layout.with_weight, use_sel=use_sel, k_class=k_class,
    )
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0,
            grid=(1,),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),  # aux
                pl.BlockSpec(memory_space=pl.ANY),  # P (alias)
            ],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                pltpu.VMEM((_URING, bandn, BLK), jnp.int32),
                pltpu.VMEM((_URING, 8, BLK), jnp.float32),
                pltpu.SemaphoreType.DMA((_URING,)),
                pltpu.SemaphoreType.DMA((_URING,)),
                pltpu.SemaphoreType.DMA((_URING,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(p.shape, jnp.int32),
        input_output_aliases={1: 0},
        interpret=interpret,
        name="update_channels",
    )(aux, p)


# ======================================================================
# pure-XLA / numpy reference implementations (CPU tests / documentation)
# ======================================================================
def unpack_bins(p, layout: PLayout, n: int) -> jnp.ndarray:
    """(N, F) uint8 bins recovered from the packed words (test helper)."""
    w = layout.W
    words = p[:w, :n]  # (W, N)
    mask = (1 << layout.bits) - 1
    cols = []
    for f in range(layout.F):
        wd, p4 = divmod(f, layout.per)
        cols.append((words[wd] >> (p4 * layout.bits)) & mask)
    return jnp.stack(cols, axis=1).astype(jnp.uint8)


def hist_ref(p, start: int, cnt: int, layout: PLayout, num_bins: int) -> jnp.ndarray:
    """Reference (XLA) histogram of a segment — same contract as hist_dyn."""
    from .histogram import build_histogram

    seg = p[:, start : start + cnt]
    bins = unpack_bins(seg, layout, cnt)
    g = jax.lax.bitcast_convert_type(seg[layout.G], jnp.float32)
    h = jax.lax.bitcast_convert_type(seg[layout.H], jnp.float32)
    sel = jax.lax.bitcast_convert_type(seg[layout.SEL], jnp.float32)
    return build_histogram(bins, g, h, sel, num_bins)


def partition_ref(p, start: int, cnt: int, feat: int, zero_bin: int, dbz: int, thr: int, is_cat: bool, layout: PLayout):
    """Reference (numpy) stable partition — the expected ROW SETS of
    split_stream (which is unordered within each side: compare sorted by
    the ROWID channel)."""
    pn = np.asarray(p)
    seg = pn[:, start : start + cnt]
    wd, p4 = divmod(feat, layout.per)
    binv = (seg[wd] >> (p4 * layout.bits)) & ((1 << layout.bits) - 1)
    fv = np.where(binv == zero_bin, dbz, binv)
    gl = (fv == thr) if is_cat else (fv <= thr)
    out = np.concatenate([seg[:, gl], seg[:, ~gl]], axis=1)
    pn = pn.copy()
    pn[:, start : start + cnt] = out
    return jnp.asarray(pn), int(gl.sum())
