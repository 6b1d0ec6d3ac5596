"""Sharded tree learner — wraps ops/grow.py's collective-aware grower in
``shard_map`` over a device mesh.

Mode mapping (TreeLearner::CreateTreeLearner, tree_learner.cpp:9-33):
  tree_learner=serial  -> plain jit (single shard)
  tree_learner=data    -> rows sharded, histogram psum
                          (DataParallelTreeLearner)
  tree_learner=feature -> rows replicated, feature search sharded
                          (FeatureParallelTreeLearner)
  tree_learner=voting  -> rows sharded, top-k voted histogram reduction
                          (VotingParallelTreeLearner)

The mesh is one axis named "data"; multi-host meshes come from
jax.distributed initialization upstream — the learner only sees the axis.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map as _shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.grow import GrowParams, GrowResult, grow_tree


def make_mesh(n_devices: Optional[int] = None) -> Mesh:
    """One-axis ("data") mesh over the local devices."""
    devs = jax.devices()
    d = n_devices if n_devices is not None else len(devs)
    return Mesh(np.array(devs[:d]), ("data",))


def _shard_map_unchecked(fn, mesh, in_specs, out_specs):
    """shard_map with replication checking off (the grower's collective
    results are replicated by construction; the checker can't always
    prove it)."""
    return _shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                      check_vma=False)


class ShardedLearner:
    """Builds and caches the shard_mapped grower for one configuration."""

    def __init__(self, mode: str, mesh: Mesh, params: GrowParams):
        assert mode in ("data", "feature", "voting")
        self.mode = mode
        self.mesh = mesh
        self.d = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
        self.params = params._replace(
            parallel=mode, axis_name="data", num_machines=self.d
        )

        row_sharded = mode in ("data", "voting")
        feature_sharded = mode == "feature"
        d = self.d

        def body(bins, grad, hess, select, fmask, meta, hyper, qscale=None):
            if feature_sharded:
                # contiguous per-shard feature ownership
                # (balanced assignment, feature_parallel_tree_learner.cpp:31-50)
                f = bins.shape[1]
                per = -(-f // d)
                own = (jnp.arange(f) // per) == jax.lax.axis_index("data")
                fmask = fmask * own.astype(fmask.dtype)
            return grow_tree(bins, grad, hess, select, fmask, meta, hyper,
                             self.params, qscale)

        rowspec = P("data") if row_sharded else P()
        in_specs = (
            P("data", None) if row_sharded else P(),  # bins
            rowspec,  # grad
            rowspec,  # hess
            rowspec,  # select
            P(),  # feature_mask
            P(),  # meta
            P(),  # hyper
        )
        if self.params.quantized:
            # quantized training: the (2,) global dequantization scales
            # ride along replicated (computed once per iteration upstream)
            in_specs = in_specs + (P(),)
        out_specs = GrowResult(
            num_splits=P(),
            leaf_id=P("data") if row_sharded else P(),
            leaf_value=P(),
            leaf_cnt=P(),
            rec_leaf=P(),
            rec_feat=P(),
            rec_thr=P(),
            rec_dbz=P(),
            rec_gain=P(),
            rec_lval=P(),
            rec_rval=P(),
            rec_lcnt=P(),
            rec_rcnt=P(),
            rec_internal_value=P(),
        )
        self._fn = jax.jit(
            _shard_map_unchecked(body, mesh, in_specs, out_specs)
        )
        self._row_sharded = row_sharded
        self._rep_consts = None  # cached replicated meta/hyper (multi-process)
        self._global_bins = None  # cached assembled bins + gmax (multi-process)

    # ------------------------------------------------------------------
    def set_plan(self, plan) -> None:
        """Shard-plan seam (parallel/shardplan.py): row ownership moved,
        so the cached assembled global bins and the allgathered max row
        count are stale — drop them; the next grow reassembles from the
        new shards (shape-keyed jit recompiles automatically)."""
        del plan  # ownership is implicit in the arrays each rank passes
        self._global_bins = None
        self._gmax = None

    # ------------------------------------------------------------------
    def grow(self, bins, grad, hess, select, feature_mask, meta, hyper,
             qscale=None) -> GrowResult:
        """Grow one tree.  In a multi-process runtime each process passes
        its OWN row block (the reference's pre_partition=true contract,
        config.h:116) with equal per-process row counts; arrays are
        assembled into global row-sharded jax.Arrays and the collectives
        inside the grower ride ICI/DCN."""
        n = bins.shape[0]
        multi = jax.process_count() > 1
        shards = self.d if not multi else self.d // jax.process_count()
        pad = (-n) % max(shards, 1) if self._row_sharded else 0
        if multi and self._row_sharded:
            # processes may hold unequal row shards; pad every process to
            # the global max so the assembled global array is rectangular
            # (bins/row-count are immutable per learner — allgather once)
            if self._global_bins is None:
                from jax.experimental import multihost_utils

                counts = np.asarray(multihost_utils.process_allgather(np.asarray(n)))
                gmax = int(counts.max())
                gmax += (-gmax) % max(shards, 1)
                self._gmax = gmax
            pad = self._gmax - n
        if pad:
            bins = jnp.pad(bins, ((0, pad), (0, 0)))
            grad = jnp.pad(grad, (0, pad))
            hess = jnp.pad(hess, (0, pad))
            select = jnp.pad(select, (0, pad))  # padded rows: select=0
        if multi:
            from .distributed import global_rows_array, replicated_array

            if self._row_sharded:
                if self._global_bins is None:
                    self._global_bins = global_rows_array(bins, self.mesh)
                bins = self._global_bins
                grad = global_rows_array(grad, self.mesh)
                hess = global_rows_array(hess, self.mesh)
                select = global_rows_array(select, self.mesh)
            else:
                if self._global_bins is None:
                    self._global_bins = replicated_array(bins, self.mesh)
                bins = self._global_bins
                grad = replicated_array(grad, self.mesh)
                hess = replicated_array(hess, self.mesh)
                select = replicated_array(select, self.mesh)
            feature_mask = replicated_array(feature_mask, self.mesh)
            # meta/hyper are loop-invariant: replicate once, not per tree
            if self._rep_consts is None:
                self._rep_consts = (
                    jax.tree_util.tree_map(lambda x: replicated_array(x, self.mesh), meta),
                    jax.tree_util.tree_map(lambda x: replicated_array(x, self.mesh), hyper),
                )
            meta, hyper = self._rep_consts
            if self.params.quantized and qscale is not None:
                qscale = replicated_array(qscale, self.mesh)
        args = (bins, grad, hess, select, feature_mask, meta, hyper)
        if self.params.quantized:
            args = args + (qscale,)
        gr = self._fn(*args)
        if multi and self._row_sharded:
            # leaf_id comes back row-sharded globally; hand the caller its
            # process-local rows (matching the rows it passed in)
            shards = sorted(
                gr.leaf_id.addressable_shards, key=lambda s: s.index[0].start or 0
            )
            local = np.concatenate([np.asarray(s.data) for s in shards])
            gr = gr._replace(leaf_id=jnp.asarray(local[:n]))
        elif pad:
            gr = gr._replace(leaf_id=gr.leaf_id[:n])
        return gr
