"""GBDT training driver — counterpart of src/boosting/gbdt.{cpp,h}
(TrainOneIter gbdt.cpp:381-495, Bagging :252-334, UpdateScore :539-562,
OutputMetric :564-622, model save/load :854-1008).

TPU-first layout: scores/gradients/hessians are device-resident
``(num_tree_per_iteration, N)`` f32 arrays; one boosting iteration runs
  objective.get_gradients  (jnp, fused elementwise)
  grow_tree                (jitted leaf-wise learner, ops/grow.py)
  add_leaf_outputs         (gather on the grower's leaf_id partition)
with only the O(num_leaves) split records returning to host per tree.
Bagging is a 0/1 row mask multiplied into the histogram kernel's select
vector — the out-of-bag rows still receive score updates because the
partition predicate covers every row (the reference needs a separate
UpdateScoreOutOfBag pass; here it is free).
"""

from __future__ import annotations

import functools
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..model.tree import Tree


class _memo:
    """Call-once wrapper: several host-path metrics on one dataset share
    a single full score transfer."""

    def __init__(self, fn):
        self.fn = fn
        self.value = None

    def __call__(self):
        if self.value is None:
            self.value = self.fn()
        return self.value

from ..obs import fence, tracer
from ..obs.audit import audit
from ..ops.grow import GrowParams, grow_tree
from ..ops.predict import add_leaf_outputs, predict_binned, predict_raw
from ..ops.split import FeatureMeta, SplitHyper
from ..model.ensemble import stack_trees
from ..utils.log import Log
from ..utils.random import Random

K_MIN_SCORE = -np.inf


class GBDT:
    """The gradient-boosting driver (class GBDT, gbdt.h:24-258)."""

    # DART overrides: its per-iteration hooks (drop/normalize) are
    # host-side and incompatible with the fused partitioned trainer.
    supports_partitioned = True
    # data-parallel fused path (GOSS needs a global top_k, not sharded yet)
    supports_partitioned_data = True
    # out-of-core streaming (boosting/ooc.py): needs the serial mask
    # grower's replayable split loop.  DART opts out — its drop state
    # re-scores dropped trees over the full matrix every iteration,
    # which would multiply streaming passes.
    supports_ooc = True

    def __init__(self):
        self.models: List[Tree] = []
        self.iter = 0
        self.num_init_iteration = 0
        self.boost_from_average_ = False
        self.train_set = None
        self.objective = None
        self.config = None
        self.max_feature_idx = 0
        self.label_idx = 0
        self._rebalance = None
        self._membership = None
        self._iter_complete = False

    # ------------------------------------------------------------------
    def init(self, config, train_set, objective, training_metrics=()):
        """GBDT::Init + ResetTrainingData (gbdt.cpp:65-218)."""
        tracer.refresh_from_env()  # LIGHTGBM_TPU_TRACE may be set per-run
        audit.refresh_from_env()  # LIGHTGBM_TPU_AUDIT split-decision trail
        self.config = config
        self.train_set = train_set
        self.objective = objective
        self.num_data = train_set.num_data
        # with a custom objective (objective=None) the class count comes
        # from config.num_class (gbdt.cpp ResetTrainingData: num_class_)
        self.num_tree_per_iteration = (
            objective.num_tree_per_iteration
            if objective is not None
            else max(config.num_class, 1)
        )
        self.num_class = config.num_class
        self.max_feature_idx = train_set.num_total_features - 1
        self.label_idx = getattr(train_set, "label_idx", 0)
        self.feature_names = train_set.feature_names
        self.training_metrics = list(training_metrics)
        self.shrinkage_rate = config.learning_rate

        # multi-host bootstrap must precede ANY device use (a backend
        # query locks in a single-process runtime) — including the
        # objective's label transfer below
        if config.tree_learner.lower() in ("data", "feature", "voting"):
            from ..parallel.distributed import ensure_initialized

            ensure_initialized(config)

        # live elastic membership (parallel/membership.py): armed only
        # when the knob is on AND a MembershipRuntime has adopted an
        # epoch (bootstrap()/join() ran before Booster construction).
        # OFF is the exact static-fleet path — zero extra collectives.
        self._membership = None
        self._membership_pauses = []  # resize stalls (spot bench p50/p99)
        if getattr(config, "elastic_membership", False):
            from ..parallel import membership as _mship

            rt = _mship.runtime()
            if rt is None:
                rt = _mship.runtime_from_env()
            if rt is None or rt.epoch < 0:
                Log.warning(
                    "elastic_membership=true ignored: no adopted "
                    "MembershipRuntime (call bootstrap()/join(), or set "
                    "LIGHTGBM_TPU_MEMBER_DIR, before training)")
            else:
                self._membership = rt

        if objective is not None:
            md = train_set.metadata
            if (md.query_boundaries is not None
                    and config.tree_learner.lower() in
                    ("data", "feature", "voting")):
                # world-invariant ranking program: pad every shard's
                # queries to the GLOBAL max group size — a dataset
                # constant under whole-group moves.  Padding to the
                # local max would tie the (Q, S, S) lambda-matrix shape
                # (and so the f32 reduction order) to the world size
                # and to every reshard; quantized stochastic rounding
                # then amplifies the ulp drift into different trees.
                import jax as _jax

                _gs = np.diff(np.asarray(md.query_boundaries, np.int64))
                local_s = int(_gs.max()) if len(_gs) else 1
                if _jax.process_count() > 1:
                    from ..parallel import collect as _collect

                    blobs = _collect.allgather_bytes(
                        local_s.to_bytes(8, "little"), "misc")
                    local_s = max(int.from_bytes(b, "little")
                                  for b in blobs)
                md.pad_group_size = local_s
            # label statistics on the host and the objective's row vectors
            # to the device: seconds at 21M rows (once a Booster)
            with tracer.stage("objective_init", rows=self.num_data):
                objective.init(train_set.metadata, self.num_data)

        # persistent compile cache, keyed on the now-known backend
        from .. import enable_compile_cache

        enable_compile_cache()

        # out-of-core routing decides BEFORE the matrix upload: when the
        # streamed path is on, the (N, F) bin matrix never becomes
        # device-resident (self.bins stays None) and only the per-row
        # vectors live on device.
        from .ooc import resolve_out_of_core

        self.ooc = None
        ooc_on, ooc_chunk_rows, ooc_why = resolve_out_of_core(config, train_set)
        if ooc_on and self._membership is not None:
            Log.fatal(
                "elastic_membership is not supported with out-of-core "
                "streaming: membership transitions reshard rows in RAM, "
                "but streamed rows are disk-resident")
        if ooc_on:
            forced = "forced" in ooc_why
            unsupported = None
            if config.tree_learner.lower() not in ("serial", "data"):
                unsupported = (
                    f"tree_learner={config.tree_learner} (streaming "
                    "supports serial, or data with per-rank shards)")
            elif not self.supports_ooc:
                unsupported = f"boosting type {type(self).__name__}"
            if unsupported is not None:
                if forced:
                    Log.fatal(
                        "out_of_core=true is not supported with %s",
                        unsupported)
                Log.warning(
                    "out-of-core auto-routing (%s) skipped: not supported "
                    "with %s; training in-memory", ooc_why, unsupported)
                ooc_on = False

        # quantized training accumulates n*QMAX in int32 (root totals and
        # psum'd histogram bins, ops/grow.py) — past the headroom it would
        # wrap silently and grow wrong trees, so decline up front
        if config.quantized_training:
            from ..ops import qhist as _qhist

            n_rows = self.num_data
            if self._membership is not None:
                # the membership runtime already carries the fleet's
                # global row count; joiners must NOT issue init-time
                # collectives (the survivors are mid-iteration)
                n_rows = int(self._membership.num_data)
            elif config.tree_learner.lower() in ("data", "feature", "voting"):
                import jax as _jax

                if _jax.process_count() > 1:
                    # the data-parallel merge sums GLOBAL rows into a
                    # bin; gather the per-rank counts over the byte
                    # collectives (works on the KV transport too, where
                    # XLA:CPU has no multi-process computations)
                    from ..parallel import collect as _collect

                    blobs = _collect.allgather_bytes(
                        int(self.num_data).to_bytes(8, "little"), "misc")
                    n_rows = sum(int.from_bytes(b, "little")
                                 for b in blobs)
            limit = _qhist.max_rows_for(config.quantized_grad_bits)
            if n_rows > limit:
                Log.warning(
                    "quantized_training disabled: %d rows exceed the "
                    "int32 histogram-accumulator headroom (%d rows at "
                    "quantized_grad_bits=%d); training on f32 gradients",
                    n_rows, limit, config.quantized_grad_bits)
                config.quantized_training = False

        # device-resident training state
        # A dataset made from sparse input holds its bundles alone: the
        # fused trainer packs those, and the (N, F) bins are decoded and
        # uploaded only if something that works by feature reads `bins`
        # (the mask grower, rollback, DART's rescoring)
        self._bins = None
        self._bins_lazy = not ooc_on and not train_set.has_dense_bins
        if not ooc_on and not self._bins_lazy:
            # ends in one wait, sink on or off: the stage would otherwise
            # read the dispatch, and the copy would land in whichever span
            # next touches the device (once a Booster, never a chunk)
            with tracer.stage("bins_upload", bytes=int(train_set.binned.nbytes)):
                self._bins = jax.block_until_ready(jnp.asarray(train_set.binned))
        self.num_bins = int(train_set.max_num_bin)
        self.meta = FeatureMeta.from_dataset(train_set)
        self.hyper = SplitHyper.from_config(config)
        # composable trainer core (tree/strategy.py): built AFTER the
        # quantized-headroom check above so the strategy reflects any
        # capability decline; rides GrowParams as a static (hashable)
        # field, so every learner picks plug-ins up through one seam
        from ..tree.strategy import TreeStrategy

        self.strategy = TreeStrategy.from_config(config, train_set)
        self.grow_params = GrowParams(
            num_leaves=config.num_leaves,
            num_bins=self.num_bins,
            max_depth=config.max_depth,
            use_missing=config.use_missing,
            top_k=config.top_k,
            quantized=config.quantized_training,
            quant_bits=config.quantized_grad_bits,
            quant_seed=config.seed,
            strategy=self.strategy,
        )
        # linear-tree state (tree/linear.py plug-in): the bin-value LUT
        # is built lazily on first fit; _linear_k pins the coefficient
        # width so every per-tree fit compiles one program shape
        self._value_lut = None
        self._linear_cat = None
        self._linear_k = None
        # tree-learner dispatch (TreeLearner::CreateTreeLearner,
        # tree_learner.cpp:9-33): serial on one chip, or a sharded learner
        # over the device mesh
        learner_type = config.tree_learner.lower()
        self.learner = None
        self.ptrainer = None
        if self._membership is not None:
            # elastic fleet: every member runs single-process JAX (the
            # jax.distributed service pins the world at init and turns
            # any peer death into an uncatchable C++ fatal), so the
            # leaf-wise loop is host-driven over the shared KV store.
            # The comm's rank/world are live properties of the epoch —
            # a transition resizes the learner with no learner change.
            from ..parallel.hostlearner import HostParallelLearner
            from ..parallel.membership import MembershipComm

            if train_set.metadata.query_boundaries is not None:
                Log.fatal(
                    "elastic_membership does not support query-grouped "
                    "(ranking) datasets yet: transitions cannot "
                    "re-derive group boundaries across the new world")
            self.learner = HostParallelLearner(
                "data", MembershipComm(self._membership), self.grow_params)
            Log.info(
                "Using host-driven elastic data-parallel learner: "
                "member=%d rank=%d/%d epoch=%d", self._membership.id,
                self._membership.rank, self._membership.nproc,
                self._membership.epoch)
        elif ooc_on:
            import jax as _jax

            if learner_type == "data" and _jax.process_count() > 1:
                # rank-sharded streaming: every rank streams its own
                # shard and node histograms merge over the hardened
                # byte collectives (boosting/oocdist.py)
                from ..parallel.comm import NetComm
                from .oocdist import DistributedOocTrainer

                self.ooc = DistributedOocTrainer(
                    train_set, config, self.grow_params, ooc_chunk_rows,
                    NetComm())
                Log.info(
                    "Using distributed out-of-core data-parallel "
                    "learner over %d processes", _jax.process_count())
            else:
                if learner_type == "data":
                    Log.warning(
                        "tree_learner=data requested with out-of-core "
                        "streaming but only one process is attached; "
                        "streaming serially")
                from .ooc import OocTrainer

                self.ooc = OocTrainer(
                    train_set, config, self.grow_params, ooc_chunk_rows)
            self.learner = self.ooc
        elif learner_type in ("data", "feature", "voting"):
            import jax as _jax

            from ..parallel import ShardedLearner, make_mesh

            nproc = _jax.process_count()
            if nproc > 1 and learner_type in ("feature", "voting"):
                # column-sharded / PV-Tree learners have no fused
                # multi-process formulation: the host drives the
                # leaf-wise loop over the hardened byte collectives
                from ..parallel.comm import NetComm
                from ..parallel.hostlearner import HostParallelLearner

                self.learner = HostParallelLearner(
                    learner_type, NetComm(), self.grow_params)
                Log.info(
                    "Using host-driven %s-parallel learner over %d "
                    "processes", learner_type, nproc)
            elif len(_jax.devices()) < 2:
                Log.warning(
                    "tree_learner=%s requested but only one device is "
                    "visible; falling back to serial", learner_type,
                )
            else:
                # data-parallel rides the partitioned fast path when
                # eligible (histogram psum per split); feature/voting
                # keep the mask grower's collective formulations
                if (learner_type == "data" and self.supports_partitioned
                        and self.supports_partitioned_data):
                    with tracer.stage("trainer_import"):
                        from .ptrainer import (
                            ShardedPartitionedTrainer,
                            eligible as _pt_eligible,
                        )

                    if _pt_eligible(config, train_set, objective,
                                    self.num_tree_per_iteration):
                        self.ptrainer = ShardedPartitionedTrainer(
                            train_set, config, objective, self.meta,
                            self.hyper, make_mesh(),
                        )
                        Log.info(
                            "Using data-parallel partitioned (fused) TPU "
                            "tree learner over %d devices",
                            self.ptrainer.d,
                        )
                if self.ptrainer is None:
                    if nproc > 1 and _jax.default_backend() == "cpu":
                        # XLA:CPU rejects multi-process computations;
                        # data-parallel runs host-driven over the KV
                        # collectives (same transport rule as collect.py)
                        from ..parallel.comm import NetComm
                        from ..parallel.hostlearner import (
                            HostParallelLearner,
                        )

                        self.learner = HostParallelLearner(
                            "data", NetComm(), self.grow_params)
                        Log.info(
                            "Using host-driven data-parallel learner "
                            "over %d processes", nproc)
                    else:
                        self.learner = ShardedLearner(
                            learner_type, make_mesh(), self.grow_params
                        )
        elif learner_type != "serial":
            Log.fatal("Unknown tree learner type %s", config.tree_learner)

        # Partitioned fused trainer (ops/pgrow.py): the TPU fast path for
        # serial single-class training with a row-local objective.  (The
        # earlier host-driven FastGrower is gone: it paid a host round
        # trip per split; pgrow supersedes it.)
        if self.learner is None and self.ptrainer is None and self.supports_partitioned:
            # a process's first import of the fused trainer loads Pallas: a
            # second of the first Booster's construction, nothing after
            with tracer.stage("trainer_import"):
                from .ptrainer import PartitionedTrainer, eligible as _pt_eligible

            if _pt_eligible(config, train_set, objective, self.num_tree_per_iteration):
                self.ptrainer = PartitionedTrainer(
                    train_set, config, objective, self.meta, self.hyper,
                    bins_dev=self._bins,
                )
                Log.info("Using partitioned (fused) TPU tree learner")
        k = self.num_tree_per_iteration
        self.scores = jnp.zeros((k, self.num_data), jnp.float32)
        init_score = train_set.metadata.init_score
        self.has_init_score = init_score is not None
        if self.has_init_score:
            self.scores = self.scores + jnp.asarray(
                np.asarray(init_score, np.float32).reshape(k, -1)
            )

        # validation sets
        self.valid_sets = []
        self.valid_bins = []
        self.valid_scores = []
        self.valid_metrics = []
        self.valid_names = []
        self.best_iter = []
        self.best_score = []
        self.best_msg = []

        # bagging state
        self.bag_rng = np.random.RandomState(config.bagging_seed)
        self.need_re_bagging = False
        self.is_bagging = (
            config.bagging_fraction < 1.0 and config.bagging_freq > 0
        )
        self.select = jnp.ones(self.num_data, jnp.float32)
        self.feature_rng = Random(config.feature_fraction_seed)
        self.full_feature_mask = jnp.ones(train_set.num_features, jnp.float32)

        # per-class "does this class have data" (SkipEmptyClass handling)
        self.class_need_train = [True] * k
        self.class_default_output = [0.0] * k

        # straggler-aware shard rebalancing (parallel/shardplan.py):
        # armed only when rebalance=true AND the learner actually owns a
        # row shard; OFF is the exact pre-existing static-shard behavior
        # (zero extra collectives)
        self._rebalance = None
        self._initial_local_rows = int(self.num_data)
        if getattr(config, "rebalance", False):
            self._init_rebalance()

        # elastic joiner: adopt the fleet's canonical state (the handoff
        # the coordinator published at admission).  No collectives here —
        # the survivors are mid-iteration when a joiner initializes.
        if self._membership is not None and self._membership.joined_mid_run:
            self._membership_join_restore()

    @property
    def bins(self):
        """The ``(N, F)`` bin matrix on the device; None when training
        streams out of core."""
        if self._bins is None and self._bins_lazy:
            self._bins_lazy = False
            self._bins = jnp.asarray(self.train_set.binned)
        return self._bins

    @bins.setter
    def bins(self, value) -> None:
        self._bins = value

    @property
    def has_device_bins(self) -> bool:
        """Whether the ``(N, F)`` matrix is on the device now (asking does
        not decode it)."""
        return self._bins is not None

    def add_valid(self, valid_set, valid_metrics, name: str):
        """GBDT::AddValidDataset (gbdt.cpp:220-250)."""
        self.valid_sets.append(valid_set)
        vb = jnp.asarray(valid_set.binned)
        self.valid_bins.append(vb)
        k = self.num_tree_per_iteration
        vs = jnp.zeros((k, valid_set.num_data), jnp.float32)
        init_score = valid_set.metadata.init_score
        if init_score is not None:
            vs = vs + jnp.asarray(np.asarray(init_score, np.float32).reshape(k, -1))
        # replay existing models onto the new valid set
        if self.models:
            arrays = stack_trees(self.models)
            for kk in range(k):
                idx = np.asarray(
                    [i * k + kk for i in range(len(self.models) // k)]
                )
                vs = vs.at[kk].add(
                    self._predict_binned_arrays(vb, arrays, idx)
                )
        self.valid_scores.append(vs)
        self.valid_metrics.append(list(valid_metrics))
        self.valid_names.append(name)
        self.best_iter.append([0] * len(valid_metrics))
        self.best_score.append([K_MIN_SCORE] * len(valid_metrics))
        self.best_msg.append([""] * len(valid_metrics))

    # ------------------------------------------------------------------
    def _boost_from_average(self):
        """gbdt.cpp:381-399 + LabelAverage (:349-379)."""
        if (
            not self.models
            and self.config.boost_from_average
            and not self.has_init_score
            and self.num_class <= 1
            and self.objective is not None
            and self.objective.boost_from_average
        ):
            label = np.asarray(self.train_set.metadata.label)
            import jax as _jax

            if self._membership is not None:
                # global label average over the live fleet (same
                # Allreduce shape as below, on the membership transport)
                sums = np.stack([
                    np.frombuffer(b, np.float64)
                    for b in self._membership.comm_allgather(
                        np.asarray([label.sum(), float(len(label))],
                                   np.float64).tobytes(),
                        what="label_average")
                ])
                init_score = float(sums[:, 0].sum() / max(sums[:, 1].sum(), 1.0))
            elif _jax.process_count() > 1:
                # distributed label average (GBDT::LabelAverage Allreduce,
                # gbdt.cpp:349-379): every process must boost from the
                # GLOBAL mean, not its local shard's
                from jax.experimental import multihost_utils

                sums = np.asarray(
                    multihost_utils.process_allgather(
                        np.asarray([label.sum(), float(len(label))])
                    )
                )
                init_score = float(sums[:, 0].sum() / max(sums[:, 1].sum(), 1.0))
            else:
                init_score = float(np.mean(label))
            tree = Tree.constant(init_score)
            self.scores = self.scores + jnp.float32(init_score)
            self.valid_scores = [vs + jnp.float32(init_score) for vs in self.valid_scores]
            if self.ptrainer is not None:
                self.ptrainer.add_score_constant(init_score)
            self.models.append(tree)
            self.boost_from_average_ = True
            Log.info("Start training from score %f", init_score)

    def _bagging(self, iter_: int) -> None:
        """Re-sample the 0/1 row mask (GBDT::Bagging, gbdt.cpp:275-334)."""
        if not self.is_bagging or iter_ % self.config.bagging_freq != 0:
            return
        bag_cnt = int(self.config.bagging_fraction * self.num_data)
        perm = self.bag_rng.permutation(self.num_data)
        mask = np.zeros(self.num_data, np.float32)
        mask[perm[:bag_cnt]] = 1.0
        self.select = jnp.asarray(mask)

    def _feature_mask(self):
        """feature_fraction sampling per tree
        (SerialTreeLearner::BeforeTrain, serial_tree_learner.cpp:236-262)."""
        frac = self.config.feature_fraction
        f = self.train_set.num_features
        if frac >= 1.0:
            return self.full_feature_mask
        used_cnt = max(1, int(f * frac))
        idx = self.feature_rng.sample(f, used_cnt)
        mask = np.zeros(f, np.float32)
        mask[idx] = 1.0
        return jnp.asarray(mask)

    def _get_gradients(self):
        """objective_->GetGradients (Boosting(), gbdt.cpp:692-700); returns
        (K, N) device arrays."""
        score = self.get_training_score()
        if self.num_tree_per_iteration == 1:
            g, h = self.objective.get_gradients(score[0])
            return g[None, :], h[None, :]
        return self.objective.get_gradients(score)

    def get_training_score(self):
        """Hook for DART's drop-then-score (GetTrainingScore)."""
        return self.scores

    # ------------------------------------------------------------------
    def train_one_iter(self, gradients=None, hessians=None, is_eval: bool = True) -> bool:
        """One boosting iteration (GBDT::TrainOneIter, gbdt.cpp:381-495).
        Returns True when training should stop.

        Under elastic membership this is a bounded retry loop: a peer
        death surfaces as ``net.PeerFailureError`` from some collective,
        the survivors negotiate a fleet resize at this boundary, and the
        iteration is replayed (or, when it already completed and only
        the boundary bookkeeping was cut short, skipped)."""
        if self._membership is None:
            return self._train_one_iter_impl(gradients, hessians, is_eval)

        from ..parallel import net as _net

        for _attempt in range(3):
            self._iter_complete = False
            try:
                return self._train_one_iter_impl(gradients, hessians, is_eval)
            except _net.PeerFailureError as e:
                self._membership_recover(e)
                if self._iter_complete:
                    # the trees of this iteration landed before the
                    # failure; only sync/eval was cut short — do not
                    # train it twice
                    return False
        raise _net.PeerFailureError(
            "membership recovery did not converge after 3 attempts")

    def _train_one_iter_impl(self, gradients=None, hessians=None,
                             is_eval: bool = True) -> bool:
        """The actual iteration body (see :meth:`train_one_iter`)."""
        if self.ptrainer is not None and gradients is None:
            return self.train_iters_partitioned(1, is_eval=is_eval)

        import time as _time

        t_iter0 = _time.perf_counter()
        if self._membership is not None:
            # boundary snapshot for exact replay: a mid-iteration peer
            # failure rolls the RNG streams, the bagging mask AND the f32
            # score caches back so the retried iteration replays from a
            # bit-identical state (device arrays are immutable, so the
            # score snapshots are reference-captures, not copies)
            self._member_iter_snapshot = {
                "bag_rng": self.bag_rng.get_state(),
                "feature_rng": self.feature_rng.get_state(),
                "select": self.select,
                "num_models": len(self.models),
                "boost_from_average": self.boost_from_average_,
                "scores": self.scores,
                "valid_scores": tuple(self.valid_scores),
            }
        self._boost_from_average()

        # comms-volume accounting: the host-driven parallel learners keep
        # an always-on purpose->bytes ledger; snapshot it around the
        # iteration so irec carries this iteration's bytes sent
        comm = getattr(self.learner, "comm", None)
        bytes_before = comm.ledger_total() if comm is not None else 0

        with tracer.iteration(self.iter) as irec:
            with tracer.span("boosting"):
                if gradients is None or hessians is None:
                    grad, hess = self._get_gradients()
                else:
                    grad = jnp.asarray(np.asarray(gradients, np.float32).reshape(
                        self.num_tree_per_iteration, -1))
                    hess = jnp.asarray(np.asarray(hessians, np.float32).reshape(
                        self.num_tree_per_iteration, -1))
                fence((grad, hess))

            with tracer.span("bagging"):
                grad, hess = self._adjust_gradients(grad, hess)
                self._bagging(self.iter)
                fence(self.select)

            should_continue = False
            leaves_grown = 0
            # quantized training (use_quantized_grad): grad/hess go to the
            # learner as stochastically-rounded int16 with a per-class
            # global scale.  The host-driven parallel learners quantize
            # internally (they must allgather the scale maxima first).
            quantize = (self.config.quantized_training
                        and not getattr(self.learner,
                                        "quantizes_internally", False))
            for k in range(self.num_tree_per_iteration):
                feature_mask = self._feature_mask()
                with tracer.span("tree"):
                    gk, hk, qscale = grad[k], hess[k], None
                    if quantize:
                        gk, hk, qscale = self._quantize_class(gk, hk, k)
                    if self.learner is not None:
                        gr = self.learner.grow(
                            self.bins, gk, hk, self.select, feature_mask,
                            self.meta, self.hyper,
                            **({"qscale": qscale} if qscale is not None
                               else {}),
                        )
                    else:
                        gr = grow_tree(
                            self.bins,
                            gk,
                            hk,
                            self.select,
                            feature_mask,
                            self.meta,
                            self.hyper,
                            self.grow_params,
                            qscale=qscale,
                        )
                    fence(gr)
                num_splits = int(gr.num_splits)
                if num_splits > 0:
                    should_continue = True
                    leaves_grown += num_splits + 1
                    tree = Tree.from_grow_result(gr, self.train_set)
                    lin_fi = lin_fv = None
                    if self.strategy.leaf_fit.linear:
                        # fit BEFORE shrinkage: the ridge solve targets
                        # the unshrunk gradients; shrinkage then scales
                        # coefficients and constant together
                        lin_fi, lin_fv = self._fit_linear_tree(
                            tree, gr, gk, hk)
                    tree.shrinkage(self.shrinkage_rate)
                    audit.record_tree(self.iter, k, gr, tree)
                    if self.strategy.split_gain.constrained:
                        # splits on constrained features ran the
                        # clipped-output gain path (ops/split.py)
                        mono_t = self.strategy.split_gain.monotone
                        rf = np.asarray(gr.rec_feat[:num_splits])
                        tracer.counter(
                            "tree.monotone_clip",
                            float(sum(1 for f in rf
                                      if mono_t[int(f)] != 0)))
                    with tracer.span("train_score"):
                        # score update via the grower's partition (one gather)
                        lv = np.zeros(self.grow_params.num_leaves, np.float32)
                        lv[: tree.num_leaves] = tree.leaf_value[: tree.num_leaves]
                        leaf_vals = jnp.asarray(lv)
                        if tree.is_linear and tree.leaf_is_linear[
                                : tree.num_leaves].any():
                            self._add_linear_train_scores(
                                tree, gr, k, lin_fi, lin_fv, leaf_vals)
                        else:
                            self.scores = self.scores.at[k].set(
                                add_leaf_outputs(self.scores[k], gr.leaf_id, leaf_vals)
                            )
                        fence(self.scores)
                    with tracer.span("valid_score"):
                        self._add_tree_to_valid_scores(tree, k)
                        fence(self.valid_scores)
                else:
                    tree = Tree(2)  # empty tree, kept for alignment
                self.models.append(tree)
            if irec is not None:
                irec["leaves"] = leaves_grown
                irec["trees"] = self.num_tree_per_iteration
                if self.is_bagging:
                    irec["bagged_rows"] = int(jnp.sum(self.select))
                if comm is not None:
                    irec["net_bytes"] = comm.ledger_total() - bytes_before

        if not should_continue:
            Log.warning(
                "Stopped training because there are no more leaves that meet "
                "the split requirements."
            )
            for _ in range(self.num_tree_per_iteration):
                self.models.pop()
            return True

        self.iter += 1
        self._iter_complete = True
        if self.ptrainer is not None:
            # scores advanced outside the partitioned channel
            self.ptrainer.score_dirty = True
        if self._rebalance is not None:
            # lockstep on every rank: the tree growing above is
            # collective, so all ranks reach this boundary together
            self._maybe_rebalance(_time.perf_counter() - t_iter0)
        if self._membership is not None:
            # membership churn drains to this same lockstep boundary
            self._maybe_membership()
        if is_eval:
            return self.eval_and_check_early_stopping()
        return False

    def train_iters_partitioned(self, num_iters: int, is_eval: bool = True) -> bool:
        """Run ``num_iters`` boosting iterations through the fused
        partitioned trainer (one device program, no per-iteration host
        round-trips).  Returns True when training should stop."""
        if num_iters <= 0:
            return False
        self._boost_from_average()
        pt = self.ptrainer
        K = self.num_tree_per_iteration
        if pt.score_dirty:
            pt.sync_scores_from(self.scores if K > 1 else self.scores[0])
        import time as _time

        t_chunk0 = _time.perf_counter()
        with tracer.span("tree"):
            recs, scores_orig, n_done = pt.train_chunk(
                num_iters, self.shrinkage_rate, self.iter
            )
        chunk_wall = _time.perf_counter() - t_chunk0
        if tracer.enabled and n_done > 0:
            # fused chunks execute as ONE device program: emit amortized
            # per-iteration records (flagged) so the trace still has an
            # iteration axis to join compile/memory signals against
            per = chunk_wall / n_done
            for t in range(n_done):
                ns = recs["num_splits"][t]
                tracer.emit_iter(
                    self.iter + t, per, {"fused_chunk": per},
                    leaves=int(np.sum(ns + (ns > 0))), trees=K,
                    amortized=True,
                )
        with tracer.span("train_score"):
            self.scores = scores_orig[None, :] if K == 1 else scores_orig
            fence(self.scores)
        chunk_trees = [[] for _ in range(K)]
        # host work between two chunk programs; `splits` is what the
        # trace's readers divide the replay's launches by, the streaming
        # kernels' counts what their operation and byte models are made of
        counts = ({"trees": n_done * K,
                   "splits": int(recs["num_splits"][:n_done].sum()),
                   **pt.stream_counts(recs, n_done)}
                  if tracer.enabled and n_done > 0 else {})
        with tracer.span("trees_from_records", **counts):
            for t in range(n_done):
                for k in range(K):
                    view = pt.grow_result_view(recs, t, k)
                    if int(view.num_splits) > 0:
                        tree = Tree.from_grow_result(view, self.train_set)
                        tree.shrinkage(self.shrinkage_rate)
                        audit.record_tree(self.iter + t, k, view, tree)
                        chunk_trees[k].append(tree)
                    else:
                        tree = Tree(2)  # empty tree, kept for class alignment
                    self.models.append(tree)
        # valid scores advance ONCE per chunk per class: a single stacked
        # predict_binned over all of the chunk's trees (vs one dispatch
        # per tree; per-dispatch cost not measured on this machine)
        with tracer.span("valid_score"):
            for k in range(K):
                if chunk_trees[k]:
                    self._add_trees_to_valid_scores(chunk_trees[k], k)
        self.iter += n_done
        if n_done < num_iters:
            Log.warning(
                "Stopped training because there are no more leaves that meet "
                "the split requirements."
            )
            return True
        if is_eval:
            return self.eval_and_check_early_stopping()
        return False

    def _adjust_gradients(self, grad, hess):
        """Hook for GOSS's gradient re-weighting; identity for GBDT."""
        return grad, hess

    def _quantize_class(self, gk, hk, k: int):
        """Quantize one class's (N,) grad/hess to int16 for the exact
        integer histogram path (ops/qhist.py).

        The scale is global over the selected rows: under a multi-process
        learner (ShardedLearner spanning hosts) the per-process abs-maxima
        are allgathered and max-reduced first, so every process derives
        the bit-identical scale — grow_tree psums the int32 histograms
        across the whole mesh, which is only meaningful when all levels
        share one scale.  The stochastic-rounding seed is value-keyed
        plus an (iteration, class) salt, so replays and row shuffles
        reproduce the same quantized vectors bit for bit."""
        import jax as _jax

        from ..ops import qhist

        bits = self.config.quantized_grad_bits
        mx = np.asarray(qhist.local_absmax(gk, hk, self.select), np.float32)
        if _jax.process_count() > 1:
            # same exchange HostParallelLearner does via its _QMAX blobs;
            # max is order-invariant, so every process agrees exactly
            from jax.experimental import multihost_utils

            mx = np.asarray(
                multihost_utils.process_allgather(mx), np.float32
            ).max(axis=0)
        qscale_np = qhist.scales_from_max(mx[0], mx[1], bits)
        seed = (int(self.config.seed) * 2654435761
                + self.iter * 97 + k * 131071 + 1) & 0xFFFFFFFF
        qscale = jnp.asarray(qscale_np)
        gq, hq = qhist.quantize_rows(gk, hk, qscale, np.uint32(seed), bits)
        return gq, hq, qscale

    def _add_tree_to_valid_scores(self, tree: Tree, k: int) -> None:
        self._add_trees_to_valid_scores([tree], k)

    def _add_trees_to_valid_scores(self, trees: List[Tree], k: int) -> None:
        if not self.valid_bins:
            return
        arrays = stack_trees(trees)
        for i, vb in enumerate(self.valid_bins):
            self.valid_scores[i] = self.valid_scores[i].at[k].add(
                self._predict_binned_arrays(vb, arrays)
            )

    def _add_tree_to_train_scores(self, tree: Tree, k: int) -> None:
        """Full binned traversal on the training set (used by rollback/DART
        where the grower's partition is no longer available)."""
        arrays = stack_trees([tree])
        if self.bins is None:
            # out-of-core: traversal is per-row, so streaming it over the
            # chunk grid is exact
            if "leaf_feat_inner" in arrays:
                arrays = dict(arrays)
                arrays["value_lut"] = self._linear_lut()[0]
            self.scores = self.scores.at[k].set(
                self.ooc.add_tree_scores(self.scores[k], arrays)
            )
            return
        self.scores = self.scores.at[k].add(
            self._predict_binned_arrays(self.bins, arrays)
        )

    # -- linear-leaf plug-in (tree/linear.py LeafFit strategy) ---------
    def _linear_lut(self):
        """Cached ``(value_lut, is_categorical)`` pair: the (F, B) f32
        bin-representative table every linear fit/score path shares, and
        the per-inner-feature categorical mask that keeps categorical
        splits out of leaf models."""
        if self._value_lut is None:
            from ..io.binning import CATEGORICAL
            from ..tree.linear import build_value_lut

            self._value_lut = jnp.asarray(
                build_value_lut(self.train_set, self.num_bins))
            self._linear_cat = np.asarray(
                [m.bin_type == CATEGORICAL
                 for m in self.train_set.bin_mappers], bool)
        return self._value_lut, self._linear_cat

    def _linear_kmax(self) -> int:
        """Pinned coefficient width: every per-tree fit pads its path
        planes to this k, so the batched Cholesky (and the OOC stats
        fold) compiles exactly one program shape per training run."""
        if self._linear_k is None:
            num_numerical = int((~self._linear_lut()[1]).sum())
            k = min(self.grow_params.num_leaves - 1, num_numerical)
            if self.config.max_depth > 0:
                k = min(k, self.config.max_depth)
            self._linear_k = max(k, 1)
        return self._linear_k

    def _fit_linear_tree(self, tree: Tree, gr, gk, hk):
        """Fit per-leaf ridge models for a freshly-grown tree (BEFORE
        shrinkage): accumulate the (L, k+1, k+1) normal equations over
        the selected rows, solve as one batched Cholesky, and attach the
        models to ``tree``.  Returns the packed (L, k) device path
        planes so the train-score update reuses them."""
        from ..tree.linear import (leaf_path_features, linear_fit_stats,
                                   pack_path_features, solve_linear_leaves)

        lut, is_cat = self._linear_lut()
        L = self.grow_params.num_leaves
        with tracer.span("tree.leaf_fit", leaves=tree.num_leaves):
            paths = leaf_path_features(gr, is_cat)
            fi, fv = pack_path_features(paths, L,
                                        k_max=self._linear_kmax())
            fi_d = jnp.asarray(fi)
            fv_d = jnp.asarray(fv)
            if self.bins is None:
                a, b = self.ooc.folder.fold_linear_stats(
                    gk, hk, self.select, gr.leaf_id, fi_d, fv_d, lut, L)
            else:
                a, b = linear_fit_stats(
                    self.bins, gk, hk, self.select, gr.leaf_id, fi_d,
                    fv_d, lut, L)
            w, ok = solve_linear_leaves(
                a, b, fv_d, gr.leaf_cnt,
                jnp.float32(self.strategy.leaf_fit.linear_lambda),
                jnp.float32(self.hyper.lambda_l2))
            w = np.asarray(w)
            tree.set_linear_models(paths, w[:, 1:], w[:, 0],
                                   np.asarray(ok), self.train_set)
        return fi_d, fv_d

    def _add_linear_train_scores(self, tree: Tree, gr, k: int, fi, fv,
                                 leaf_vals) -> None:
        """Train-score update for a linear tree via the grower's
        partition: linear leaves evaluate their (shrunk) model at the
        bin-representative values, constant-fallback leaves add the
        classic leaf output (``leaf_vals`` is the padded fallback
        plane)."""
        from ..tree.linear import linear_leaf_scores

        lut = self._linear_lut()[0]
        L, kw = fi.shape
        coeff = np.zeros((L, kw), np.float32)
        const = np.zeros(L, np.float32)
        isl = np.zeros(L, bool)
        for i in range(tree.num_leaves):
            if tree.leaf_is_linear[i]:
                cs = tree.leaf_coeff[i]
                coeff[i, : len(cs)] = cs
                const[i] = tree.leaf_const[i]
                isl[i] = True
        coeff_d = jnp.asarray(coeff)
        const_d = jnp.asarray(const)
        isl_d = jnp.asarray(isl)
        if self.bins is None:
            self.scores = self.scores.at[k].set(
                self.ooc.folder.fold_linear_scores(
                    self.scores[k], gr.leaf_id, fi, fv, coeff_d,
                    const_d, leaf_vals, isl_d, lut)
            )
            return
        self.scores = self.scores.at[k].add(
            linear_leaf_scores(self.bins, gr.leaf_id, fi, fv, coeff_d,
                               const_d, leaf_vals, isl_d, lut)
        )

    def _predict_binned_arrays(self, bins, arrays, idx=None):
        """Stacked-tree binned scoring, routed through the linear
        traversal when the stack carries linear-leaf planes
        (model/ensemble.py emits them only then) — constant ensembles
        keep the exact pre-strategy ``predict_binned`` dispatch."""
        def sel(name):
            a = arrays[name]
            return a if idx is None else a[idx]

        planes = (
            sel("split_feature_inner"), sel("threshold_bin"),
            sel("zero_bin"), sel("default_bin_for_zero"),
            sel("is_categorical"), sel("left_child"),
            sel("right_child"), sel("leaf_value"),
        )
        if "leaf_feat_inner" not in arrays:
            return predict_binned(bins, *planes)
        from ..tree.linear import predict_linear_binned

        return predict_linear_binned(
            bins, *planes, sel("leaf_feat_inner"), sel("leaf_feat_valid"),
            sel("leaf_coeff"), sel("leaf_const"), sel("leaf_is_linear"),
            self._linear_lut()[0])

    def rollback_one_iter(self) -> None:
        """GBDT::RollbackOneIter (gbdt.cpp:497-514)."""
        if self.iter <= 0:
            return
        k = self.num_tree_per_iteration
        last = self.models[-k:]
        for tree_id, tree in enumerate(last):
            tree.shrinkage(-1.0)
            self._add_tree_to_train_scores(tree, tree_id)
            for i in range(len(self.valid_bins)):
                arrays = stack_trees([tree])
                self.valid_scores[i] = self.valid_scores[i].at[tree_id].add(
                    self._predict_binned_arrays(self.valid_bins[i], arrays)
                )
        del self.models[-k:]
        self.iter -= 1
        if self.ptrainer is not None:
            # keep the partitioned score channel consistent (the segment
            # layout still matches the popped tree, so this is one cheap
            # in-place subtract; otherwise resync lazily)
            if not self.ptrainer.rollback_last():
                self.ptrainer.score_dirty = True

    # ------------------------------------------------------------------
    def eval_and_check_early_stopping(self) -> bool:
        """EvalAndCheckEarlyStopping + OutputMetric (gbdt.cpp:516-622)."""
        best_msg = self._output_metric(self.iter)
        if best_msg:
            Log.info(
                "Early stopping at iteration %d, the best iteration round is %d",
                self.iter,
                self.iter - self.config.early_stopping_round,
            )
            Log.info("Output of best iteration round:\n%s", best_msg)
            n_pop = self.config.early_stopping_round * self.num_tree_per_iteration
            del self.models[len(self.models) - n_pop:]
            return True
        return False

    def _train_score_host(self):
        return np.asarray(self.scores, np.float64)

    def _valid_score_host(self, i):
        return np.asarray(self.valid_scores[i], np.float64)

    def _metric_score(self, score):
        """(K, N) -> what metrics expect: (N,) when single-class."""
        return score[0] if score.shape[0] == 1 else score

    def _eval_metric(self, m, score_dev, host_fn):
        """Evaluate one metric, preferring its device twin (metric/
        device.py): keeps the (K, N) scores device-resident and transfers
        one scalar instead of pulling + sorting the full vector on host.
        ``host_fn`` should be a ``_memo``-wrapped puller so several
        host-path metrics on one dataset share a single transfer."""
        if getattr(type(m), "_dev_fn", None) is not None:
            try:
                return m.eval_device(self._metric_score(score_dev), self.objective)
            except Exception:  # pragma: no cover - fall back to host path
                pass
        return m.eval(self._metric_score(host_fn()), self.objective)

    def _output_metric(self, iter_: int) -> str:
        es_round = self.config.early_stopping_round
        need_output = (iter_ % self.config.output_freq) == 0
        msg_parts = []
        ret = ""
        if need_output and self.training_metrics:
            host_fn = _memo(self._train_score_host)
            for m in self.training_metrics:
                for name, val in self._eval_metric(m, self.scores, host_fn):
                    line = f"Iteration:{iter_}, training {name} : {val:g}"
                    Log.info("%s", line)
                    if es_round > 0:
                        msg_parts.append(line)
        meet = []
        if need_output or es_round > 0:
            for i in range(len(self.valid_metrics)):
                host_fn = _memo(functools.partial(self._valid_score_host, i))
                for j, m in enumerate(self.valid_metrics[i]):
                    results = self._eval_metric(m, self.valid_scores[i], host_fn)
                    for name, val in results:
                        line = f"Iteration:{iter_}, valid_{i+1} {name} : {val:g}"
                        if need_output:
                            Log.info("%s", line)
                        if es_round > 0:
                            msg_parts.append(line)
                    if not ret and es_round > 0:
                        factor = 1.0 if m.bigger_is_better else -1.0
                        cur = factor * results[-1][1]
                        if cur > self.best_score[i][j]:
                            self.best_score[i][j] = cur
                            self.best_iter[i][j] = iter_
                            meet.append((i, j))
                        elif iter_ - self.best_iter[i][j] >= es_round:
                            ret = self.best_msg[i][j]
        msg = "\n".join(msg_parts)
        for i, j in meet:
            self.best_msg[i][j] = msg
        return ret

    def get_eval_at(self, data_idx: int):
        """GBDT::GetEvalAt — [(name, value, bigger_is_better), ...] for
        callbacks/early stopping."""
        out = []
        if data_idx == 0:
            score_dev, host_fn = self.scores, _memo(self._train_score_host)
            metrics = self.training_metrics
        else:
            score_dev = self.valid_scores[data_idx - 1]
            host_fn = _memo(functools.partial(self._valid_score_host, data_idx - 1))
            metrics = self.valid_metrics[data_idx - 1]
        for m in metrics:
            for name, val in self._eval_metric(m, score_dev, host_fn):
                out.append((name, val, m.bigger_is_better))
        return out

    # ------------------------------------------------------------------
    # ------------------------------------------------------------------
    # straggler-aware shard rebalancing (parallel/shardplan.py)
    # ------------------------------------------------------------------
    def _rebalance_gather(self, blob: bytes):
        """The rebalance control-plane allgather: membership fleets ride
        the epoch-aware learner comm (jax.process_count() is 1 there);
        static fleets keep the exact pre-existing byte collectives."""
        if self._membership is not None:
            return self.learner.comm.allgather(blob, purpose="rebalance")
        from ..parallel.collect import allgather_bytes

        return allgather_bytes(blob, purpose="rebalance")

    def _init_rebalance(self) -> None:
        """Arm the rebalance controller when this run actually owns a
        row shard; otherwise log why the knob is ignored."""
        import jax as _jax

        from ..parallel.hostlearner import HostParallelLearner

        rt = self._membership
        nproc = rt.nproc if rt is not None else _jax.process_count()
        md = self.train_set.metadata
        why = None
        if nproc <= 1 and rt is None:
            why = "single process (nothing to rebalance)"
        elif self.ptrainer is not None:
            why = "fused partitioned trainer (static device layout)"
        elif self.ooc is not None:
            why = "out-of-core streaming (rows are disk-resident)"
        elif self.learner is None:
            why = "serial learner"
        elif (isinstance(self.learner, HostParallelLearner)
              and self.learner.mode == "feature"):
            why = "feature-parallel learner (columns are sharded, not rows)"
        elif md.init_score is not None:
            why = "per-row init_score is not relocatable yet"
        if why is not None:
            Log.warning("rebalance=true ignored: %s", why)
            return
        from ..parallel.shardplan import RebalanceController, ShardPlan

        if rt is not None:
            counts = list(rt.counts)
            rank = rt.rank
        else:
            counts = [
                int.from_bytes(g, "little")
                for g in self._rebalance_gather(
                    int(self.num_data).to_bytes(8, "little"))
            ]
            rank = _jax.process_index()
        group_bounds = None
        if md.query_boundaries is not None:
            # query-grouped data (lambdarank): moves snap to whole query
            # groups, so exchange the per-rank group sizes once and keep
            # the cumulative GLOBAL group boundaries in the controller
            sizes = np.diff(np.asarray(md.query_boundaries, np.int64))
            blobs = self._rebalance_gather(
                np.ascontiguousarray(sizes, np.int64).tobytes())
            all_sizes = np.concatenate(
                [np.frombuffer(b, np.int64) for b in blobs])
            group_bounds = np.concatenate(([0], np.cumsum(all_sizes)))
        self._rebalance = {
            "plan": ShardPlan.from_counts(counts),
            "ctl": RebalanceController(
                threshold=self.config.rebalance_threshold,
                patience=self.config.rebalance_patience,
                max_move_frac=self.config.rebalance_max_move_frac,
                group_bounds=group_bounds,
            ),
            "rank": rank,
            "group_bounds": group_bounds,
        }
        Log.info(
            "Shard rebalancing armed: shards=%s threshold=%.2f "
            "patience=%d max_move_frac=%.2f groups=%s", counts,
            self.config.rebalance_threshold,
            self.config.rebalance_patience,
            self.config.rebalance_max_move_frac,
            "whole-query" if group_bounds is not None else "row",
        )

    def _maybe_rebalance(self, wall_s: float) -> None:
        """Once per iteration, in lockstep on every rank: exchange the
        tiny per-rank compute/wait/heartbeat table, run the identical
        deterministic controller on it, and apply the plan it proposes
        at this iteration boundary."""
        import json as _json

        from ..parallel import net as _net

        rb = self._rebalance
        wait_s = _net.wait_clock_drain()
        compute_s = max(wall_s - wait_s, 0.0)
        hb_age = 0.0
        watch = (self._membership.watch if self._membership is not None
                 else _net.peer_watch())
        if watch is not None:
            ages = watch.ages()
            if ages:
                hb_age = max(float(v) for v in ages.values())
        entry = {"compute_s": compute_s, "wait_s": wait_s,
                 "hb_age": hb_age}
        table = [
            _json.loads(g)
            for g in self._rebalance_gather(_json.dumps(entry).encode())
        ]
        plan = rb["plan"]
        new_plan = rb["ctl"].observe(
            plan,
            [t["compute_s"] for t in table],
            [t["hb_age"] for t in table],
        )
        if new_plan is None:
            return
        tracer.event(
            "rebalance.trigger", iter=self.iter,
            compute_s=[round(float(t["compute_s"]), 4) for t in table],
            wait_s=[round(float(t["wait_s"]), 4) for t in table],
        )
        self._apply_rebalance(plan, new_plan)

    def _apply_rebalance(self, old_plan, new_plan) -> None:
        """Move row blocks to the new plan — 'checkpoint reshape in
        RAM': the same contiguous-slice semantics as the elastic restore
        path (ckpt/state.py reshard_to_local), applied to the live
        dataset/score/bagging state, then every row-derived binding is
        refreshed."""
        from ..parallel import net as _net
        from ..parallel.shardplan import exchange_rows

        rank = self._rebalance["rank"]
        md = self.train_set.metadata
        blocks = {
            "binned": (np.asarray(self.train_set.binned), 0),
            "label": (np.asarray(md.label), 0),
            "scores": (np.asarray(self.scores, np.float32), 1),
            "select": (np.asarray(self.select, np.float32), 0),
        }
        if md.weights is not None:
            blocks["weights"] = (np.asarray(md.weights), 0)
        if getattr(self.train_set, "bundled", None) is not None:
            blocks["bundled"] = (np.asarray(self.train_set.bundled), 0)
        comm = (self.learner.comm if self._membership is not None
                else None)
        moved = exchange_rows(old_plan, new_plan, rank, blocks, comm=comm)
        n_new = int(new_plan.counts[rank])

        self.train_set.binned = moved["binned"]
        if "bundled" in moved:
            self.train_set.bundled = moved["bundled"]
        md.num_data = n_new
        md.label = moved["label"]
        if "weights" in moved:
            md.weights = moved["weights"]
        # the shard's rows changed: cached checkpoint fingerprints are
        # stale (the GLOBAL fingerprint is invariant — contiguous
        # rank-ordered partition is preserved)
        for attr in ("_ckpt_fingerprint", "_ckpt_fp_parts"):
            if getattr(self.train_set, attr, None) is not None:
                setattr(self.train_set, attr, None)

        self.num_data = n_new
        if self.bins is not None:
            self.bins = jnp.asarray(self.train_set.binned)
        self.scores = jnp.asarray(moved["scores"])
        self.select = jnp.asarray(moved["select"])
        gb = self._rebalance.get("group_bounds")
        if gb is not None:
            # whole-group cuts (snap_to_groups) guarantee the new range
            # starts and ends on global group boundaries: re-derive the
            # local query layout before the objective re-binds it
            s, e = new_plan.rank_range(rank)
            local_b = gb[(gb >= s) & (gb <= e)]
            md.set_query(np.diff(local_b))
        # objective/metrics bind per-row device arrays at init
        if self.objective is not None:
            self.objective.init(md, n_new)
        for metric in self.training_metrics:
            metric.init(md, n_new)
        if self.learner is not None and hasattr(self.learner, "set_plan"):
            self.learner.set_plan(new_plan)
        self._rebalance["plan"] = new_plan
        if self._membership is not None:
            # rt.counts mirrors the epoch record, which only refreshes at
            # epoch commits — but eviction synthesis reads it as the LIVE
            # row layout.  Every member applies the identical plan in
            # lockstep (the controller is deterministic), so updating it
            # here keeps the whole fleet's view consistent mid-epoch.
            self._membership.counts = tuple(int(c) for c in new_plan.counts)
        # injected per-collective delays model per-row-slow hosts: their
        # stall shrinks with the rank's row share
        _net.set_delay_scale(n_new / max(self._initial_local_rows, 1))
        moved_rows = sum(
            max(0, a - b) for a, b in zip(old_plan.counts, new_plan.counts)
        )
        tracer.counter("rebalance.move_rows", float(moved_rows))
        tracer.event("rebalance.plan", iter=self.iter,
                     before=list(old_plan.counts),
                     after=list(new_plan.counts))
        Log.info("Rebalanced shards at iteration %d: %s -> %s "
                 "(%d rows moved)", self.iter, list(old_plan.counts),
                 list(new_plan.counts), moved_rows)

    # ------------------------------------------------------------------
    # live elastic membership (parallel/membership.py)
    # ------------------------------------------------------------------
    def _maybe_membership(self) -> None:
        """Iteration-boundary membership sync, in lockstep on every
        member: a tiny intent allgather; on churn, drain into an epoch
        transition at this boundary."""
        decision = self._membership.sync()
        if decision is not None:
            self._apply_membership_change(decision)

    def _membership_recover(self, err) -> None:
        """A collective raised PeerFailureError: roll the partially-grown
        iteration back, converge on who is still alive, and resize."""
        rt = self._membership
        dead = tuple(r for r in getattr(err, "ranks", ()) if r != rt.id)
        Log.warning(
            "Peer failure under elastic membership: %s — negotiating a "
            "fleet resize (evidence: %s)", err, list(dead))
        if not self._iter_complete:
            self._membership_rollback_partial()
        decision = rt.sync(known_dead=dead)
        if decision is not None:
            self._apply_membership_change(decision)

    def _membership_rollback_partial(self) -> None:
        """Undo partially-grown iteration state left by a mid-grow peer
        failure so the retry replays from the boundary.  The boundary
        snapshot restores the score caches by reference, so the retry is
        bit-identical to a fleet that never saw the failure — including
        multi-class iterations, where arithmetically un-adding a tree
        would not round-trip (fl(fl(a+v)-v) != a in general).  The
        subtraction fallback only covers paths that never took a
        snapshot (e.g. the fused partitioned trainer's)."""
        snap = getattr(self, "_member_iter_snapshot", None)
        if snap is not None:
            # a first-iteration failure may land after _boost_from_average
            # ran: the snapshot predates it, so the constant tree and its
            # score shift roll back too and the retry re-derives the
            # global average on the resized fleet (same bytes — the
            # average is over the invariant global dataset)
            del self.models[snap["num_models"]:]
            self.boost_from_average_ = snap["boost_from_average"]
            self.scores = snap["scores"]
            self.valid_scores = list(snap["valid_scores"])
            self.bag_rng.set_state(snap["bag_rng"])
            self.feature_rng.set_state(snap["feature_rng"])
            self.select = snap["select"]
            return
        k = self.num_tree_per_iteration
        complete = self.iter * k + (1 if self.boost_from_average_ else 0)
        extra = self.models[complete:]
        for kk, tree in enumerate(extra):
            if tree.num_leaves > 1:
                tree.shrinkage(-1.0)
                self._add_tree_to_train_scores(tree, kk)
                self._add_tree_to_valid_scores(tree, kk)
        del self.models[complete:]

    def _membership_capture(self):
        """Snapshot this member's TrainState (ckpt.capture without the
        Booster wrapper — same meta contract, so the canonical merge /
        reshard machinery applies unchanged)."""
        from ..ckpt.state import (FORMAT_VERSION, TrainState,
                                  config_fingerprint, data_fingerprint,
                                  data_fingerprint_parts, pack_trees)

        arrays, py = self.export_train_state()
        arrays.update(pack_trees(self.models))
        meta = {
            "format_version": FORMAT_VERSION,
            "iteration": int(self.iter),
            "boosting_type": type(self).__name__.lower(),
            "num_models": len(self.models),
            "num_tree_per_iteration": int(self.num_tree_per_iteration),
            "num_data": int(self.num_data),
            "config_fingerprint": config_fingerprint(self.config),
            "data_fingerprint": data_fingerprint(self.train_set),
            "data_fingerprint_parts": data_fingerprint_parts(self.train_set),
            "num_valid": len(self.valid_scores),
            "best_iteration": -1,
        }
        return TrainState(meta, py, arrays)

    def _membership_replay_scores(self, binned) -> np.ndarray:
        """Re-derive a (K, n) f32 score cache for re-binned rows by
        replaying every tree in training accumulation order — one f32
        add per tree, the exact sequence the rows' original owner ran,
        so the replay is bit-identical to the scores it lost."""
        k = self.num_tree_per_iteration
        bins = jnp.asarray(binned)
        scores = jnp.zeros((k, binned.shape[0]), jnp.float32)
        offset = 1 if self.boost_from_average_ else 0
        for i, tree in enumerate(self.models):
            if tree.num_leaves <= 1:
                continue  # empty alignment tree: nothing was added
            kk = 0 if i < offset else (i - offset) % k
            arrays = stack_trees([tree])
            scores = scores.at[kk].add(
                self._predict_binned_arrays(bins, arrays))
        return np.asarray(scores, np.float32)

    def _membership_synthesize(self, member: int, own_state):
        """Reconstruct an evicted (SIGKILLed) member's TrainState without
        its participation: regenerate its rows through the row_provider
        seam, re-bin them with this member's mappers (identical on every
        member — the pre-partition contract), and replay the score cache.
        Deterministic, so every survivor synthesizes identical bytes."""
        from ..ckpt.state import TrainState, combine_fingerprint_parts
        from ..io.dataset import _bin_matrix
        from ..parallel import net as _net
        from ..parallel.shardplan import ShardPlan

        rt = self._membership
        if rt.row_provider is None:
            raise _net.PeerFailureError(
                f"cannot synthesize evicted member {member}'s shard: no "
                "row_provider seam armed (MembershipRuntime.row_provider)")
        if self.valid_scores:
            raise _net.PeerFailureError(
                "eviction with registered valid sets is not supported: "
                "the dead member's valid-score shard is unrecoverable")
        if type(self).__name__.lower() != "gbdt" and not getattr(
                self, "supports_membership_synthesis", False):
            raise _net.PeerFailureError(
                f"eviction under boosting type {type(self).__name__} is "
                "not supported: score replay assumes immutable past trees")
        # the LIVE layout, not the epoch record: a runtime rebalance moves
        # rows mid-epoch, so when the rebalancer is armed its applied plan
        # is authoritative (rt.counts is also kept in sync by
        # _apply_rebalance — this guards against any reader that isn't)
        old_plan = (self._rebalance["plan"] if self._rebalance is not None
                    else ShardPlan.from_counts(rt.counts))
        lo, hi = old_plan.rank_range(rt.members.index(member))
        X, y = rt.row_provider(lo, hi)
        ts = self.train_set
        binned = _bin_matrix(np.asarray(X, np.float64), ts.bin_mappers,
                             ts.used_feature_map)
        label = np.asarray(y, np.asarray(ts.metadata.label).dtype)
        n = int(binned.shape[0])
        import zlib as _zlib

        lab_bytes = np.ascontiguousarray(label).tobytes()
        parts = {
            "rows": n, "cols": int(binned.shape[1]),
            "crc_binned": _zlib.crc32(
                np.ascontiguousarray(binned).tobytes()) & 0xFFFFFFFF,
            "len_binned": int(binned.nbytes),
            "crc_label": _zlib.crc32(lab_bytes) & 0xFFFFFFFF,
            "len_label": len(lab_bytes),
        }
        rs = np.random.RandomState(self.config.bagging_seed)
        st = rs.get_state()
        arrays = dict(own_state.arrays)
        arrays["scores"] = self._membership_replay_scores(binned)
        # bagging-off fleets never mutate the mask; under bagging the
        # dead member's live mask is unrecoverable, so the reshard path's
        # need_re_bagging forces a fresh draw before the mask is used
        arrays["select"] = np.ones(n, np.float32)
        arrays["bag_rng_keys"] = np.asarray(st[1], np.uint32)
        py = dict(own_state.py)
        py["bag_rng"] = [str(st[0]), int(st[2]), int(st[3]), float(st[4])]
        py["need_re_bagging"] = True
        meta = dict(own_state.meta)
        meta["num_data"] = n
        meta["data_fingerprint"] = combine_fingerprint_parts([parts])
        meta["data_fingerprint_parts"] = parts
        meta["best_iteration"] = -1
        return TrainState(meta, py, arrays)

    def _apply_membership_change(self, decision) -> None:
        """One epoch transition, at an iteration boundary: gather every
        living participant's TrainState, synthesize the evicted ones,
        merge to the canonical global layout, commit the new epoch, and
        reshard to this member's new slice — all in RAM, the PR-15
        restart-time path made a runtime event."""
        import time as _time

        from ..ckpt import state as _ckpt
        from ..parallel import membership as _mship
        from ..parallel.shardplan import ShardPlan, _largest_remainder

        rt = self._membership
        t0 = _time.perf_counter()
        own = self._membership_capture()
        blobs = rt.gather_states(own.to_bytes(), decision.participants)
        states = dict(zip(decision.participants,
                          (_ckpt.TrainState.from_bytes(b) for b in blobs)))
        for d in decision.dead:
            states[d] = self._membership_synthesize(d, own)
        ordered = [states[m] for m in rt.members]
        canonical = _ckpt.merge_to_canonical(ordered)
        if rt.id in decision.leavers:
            # shard handed off; unwind out of the training loop
            raise _mship.CleanLeave(rt.epoch + 1)
        world = len(decision.new_members)
        total = int(canonical.meta["num_data"])
        counts = _largest_remainder([total / world] * world, total)
        handoff = canonical.to_bytes() if decision.joiners else None
        rt.commit_epoch(decision, counts, self.iter, total, handoff)
        self._membership_adopt(canonical, counts)
        pause = _time.perf_counter() - t0
        tracer.gauge("member.resize_pause_s", pause)
        self._membership_pauses.append(pause)
        Log.info(
            "Membership epoch %d at iteration %d: members=%s counts=%s "
            "(rank %d/%d)", rt.epoch, self.iter, list(rt.members),
            list(counts), rt.rank, rt.nproc)

    def _membership_adopt(self, canonical, counts) -> None:
        """Regenerate this member's new slice and restore its training
        state from the canonical container (reshard in RAM)."""
        from ..ckpt import state as _ckpt
        from ..io.dataset import _bin_matrix
        from ..parallel import collect as _collect
        from ..parallel import net as _net
        from ..parallel.shardplan import ShardPlan

        rt = self._membership
        # scope any collect.py gathers this process issues from here on
        # to the adopted epoch (fresh uid subtree — net.epoch_uid)
        _collect.set_epoch(rt.epoch)
        plan = ShardPlan.from_counts(counts)
        lo, hi = plan.rank_range(rt.rank)
        ts = self.train_set
        md = ts.metadata
        X, y = rt.row_provider(lo, hi)
        ts.binned = _bin_matrix(np.asarray(X, np.float64), ts.bin_mappers,
                                ts.used_feature_map)
        md.num_data = hi - lo
        md.set_label(np.asarray(y))
        for attr in ("_ckpt_fingerprint", "_ckpt_fp_parts"):
            if getattr(ts, attr, None) is not None:
                setattr(ts, attr, None)
        self.num_data = hi - lo
        if self.bins is not None:
            self.bins = jnp.asarray(ts.binned)
        # membership remaps member ids to ranks at every epoch: never
        # resume a sibling's per-rank stream — force the resized path
        canonical.meta.pop("shard_rows", None)
        local_fp = _ckpt.combine_fingerprint_parts(
            [_ckpt.data_fingerprint_parts(ts)])
        state = _ckpt.reshard_to_local(
            canonical, rt.rank, list(counts), [], local_fp,
            bag_seed=self.config.bagging_seed)
        self.models = _ckpt.unpack_trees(state.arrays)
        self.import_train_state(state.arrays, state.py)
        if self.objective is not None:
            self.objective.init(md, self.num_data)
        for metric in self.training_metrics:
            metric.init(md, self.num_data)
        if self.learner is not None and hasattr(self.learner, "set_plan"):
            self.learner.set_plan(plan)
        _net.set_delay_scale(self.num_data / max(self._initial_local_rows, 1))
        if self._rebalance is not None:
            self._rebalance["plan"] = plan
            self._rebalance["rank"] = rt.rank
            self._rebalance["ctl"].reset()

    def _membership_join_restore(self) -> None:
        """Mid-run joiner: adopt the canonical handoff the coordinator
        published at admission.  The worker already built its Dataset for
        the admitted slice, so this only restores trees + train state."""
        from ..ckpt import state as _ckpt

        rt = self._membership
        if int(rt.counts[rt.rank]) != int(self.num_data):
            Log.fatal(
                "elastic join: this worker holds %d rows but epoch %d "
                "assigns rank %d %d rows", self.num_data, rt.epoch,
                rt.rank, int(rt.counts[rt.rank]))
        canonical = _ckpt.TrainState.from_bytes(rt.read_handoff())
        own_fp = _ckpt.config_fingerprint(self.config)
        theirs = canonical.meta.get("config_fingerprint")
        if theirs is not None and theirs != own_fp:
            Log.fatal(
                "elastic join: this worker's training config (fingerprint "
                "%s) differs from the fleet's (%s) — a joiner must run the "
                "identical parameters", own_fp, theirs)
        canonical.meta.pop("shard_rows", None)
        local_fp = _ckpt.combine_fingerprint_parts(
            [_ckpt.data_fingerprint_parts(self.train_set)])
        state = _ckpt.reshard_to_local(
            canonical, rt.rank, list(rt.counts), [], local_fp,
            bag_seed=self.config.bagging_seed)
        self.models = _ckpt.unpack_trees(state.arrays)
        self.import_train_state(state.arrays, state.py)
        Log.info(
            "Joined fleet at epoch %d, iteration %d: rank %d/%d, %d "
            "rows, %d trees", rt.epoch, self.iter, rt.rank, rt.nproc,
            self.num_data, len(self.models))

    def export_train_state(self):
        """Checkpoint hook (ckpt/state.py): everything beyond the
        config/dataset/trees that the next iteration reads — score
        caches, the bagging/feature RNG streams, the live bagging mask,
        early-stopping bests.  Subclasses extend via super().

        Returns ``(arrays, py)``: numpy arrays for the npz payload and a
        JSON-serializable dict."""
        arrays = {
            "scores": np.asarray(self.scores, np.float32),
            "select": np.asarray(self.select, np.float32),
        }
        for i, vs in enumerate(self.valid_scores):
            arrays[f"valid_scores_{i}"] = np.asarray(vs, np.float32)
        st = self.bag_rng.get_state()
        arrays["bag_rng_keys"] = np.asarray(st[1], np.uint32)
        py = {
            "iter": int(self.iter),
            "num_init_iteration": int(self.num_init_iteration),
            "boost_from_average": bool(self.boost_from_average_),
            "shrinkage_rate": float(self.shrinkage_rate),
            "bag_rng": [str(st[0]), int(st[2]), int(st[3]), float(st[4])],
            "feature_rng": self.feature_rng.get_state(),
            "need_re_bagging": bool(self.need_re_bagging),
            "best_iter": [list(b) for b in self.best_iter],
            "best_score": [list(b) for b in self.best_score],
            "best_msg": [list(b) for b in self.best_msg],
            "class_need_train": list(self.class_need_train),
            "class_default_output": list(self.class_default_output),
        }
        if self.ptrainer is not None:
            arrays["pt_rowid"] = self.ptrainer.export_perm()
        return arrays, py

    def import_train_state(self, arrays, py) -> None:
        """Inverse of :meth:`export_train_state`; ``self.models`` is
        restored by the caller (ckpt/state.py unpacks the tree arrays)
        before this runs."""
        self.iter = int(py["iter"])
        self.num_init_iteration = int(py["num_init_iteration"])
        self.boost_from_average_ = bool(py["boost_from_average"])
        self.shrinkage_rate = float(py["shrinkage_rate"])
        self.scores = jnp.asarray(np.asarray(arrays["scores"], np.float32))
        self.select = jnp.asarray(np.asarray(arrays["select"], np.float32))
        for i in range(len(self.valid_scores)):
            self.valid_scores[i] = jnp.asarray(
                np.asarray(arrays[f"valid_scores_{i}"], np.float32)
            )
        name, pos, has_gauss, cached = py["bag_rng"]
        self.bag_rng.set_state(
            (str(name), np.asarray(arrays["bag_rng_keys"], np.uint32),
             int(pos), int(has_gauss), float(cached))
        )
        self.feature_rng.set_state(py["feature_rng"])
        self.need_re_bagging = bool(py["need_re_bagging"])
        self.best_iter = [list(map(int, b)) for b in py["best_iter"]]
        self.best_score = [list(map(float, b)) for b in py["best_score"]]
        self.best_msg = [list(map(str, b)) for b in py["best_msg"]]
        self.class_need_train = list(py["class_need_train"])
        self.class_default_output = list(py["class_default_output"])
        if self.learner is not None and hasattr(self.learner, "_qiter"):
            # internally-quantizing learners draw per-tree stochastic-
            # rounding seeds from a tree counter; re-anchor it to the
            # restored model list so a resumed run rounds exactly like
            # one that never died (counter increments before use, one
            # grow per appended model including empty alignment trees)
            self.learner._qiter = len(self.models) - 1
        if self.ptrainer is not None:
            if "pt_rowid" in arrays:
                self.ptrainer.import_perm(np.asarray(arrays["pt_rowid"]))
            # score channels re-sync from the restored original-order
            # scores at the next chunk (exact: channels are zero here)
            self.ptrainer.score_dirty = True

    def refresh_config(self) -> None:
        """Re-derive the config-dependent training state after a parameter
        reset (ResetConfig path used by callback.reset_parameter)."""
        self.hyper = SplitHyper.from_config(self.config)
        if self.ptrainer is not None:
            # the compiled chunk programs bake hyper/config in as closure
            # constants — swap state and drop the program cache
            self.ptrainer.hyper = self.hyper
            self.ptrainer.config = self.config
            self.ptrainer._progs.clear()
        self.shrinkage_rate = self.config.learning_rate
        self.is_bagging = (
            self.config.bagging_fraction < 1.0 and self.config.bagging_freq > 0
        )
        if not self.is_bagging:
            self.select = jnp.ones(self.num_data, jnp.float32)

    # ------------------------------------------------------------------
    @property
    def num_trees(self) -> int:
        return len(self.models)

    def current_iteration(self) -> int:
        return self.iter + self.num_init_iteration

    def _used_models(self, num_iteration: int = -1):
        num_used = len(self.models)
        if num_iteration > 0:
            ni = num_iteration + (1 if self.boost_from_average_ else 0)
            num_used = min(ni * self.num_tree_per_iteration, num_used)
        return self.models[:num_used]

    def predict_raw_scores(self, data: np.ndarray, num_iteration: int = -1) -> np.ndarray:
        """(num_pred, N) raw scores over raw (unbinned) features, batched
        on device (GBDT::PredictRaw).

        Batches go through the serving layer's shape-bucketed compile
        cache (serve/compilecache.py): N is padded up a power-of-two
        bucket ladder so repeated ad-hoc predicts at varying N reuse a
        small fixed set of compiled programs instead of recompiling per
        shape; padding rows are stripped before returning and never
        change real rows' outputs (row-independent traversal).  Set
        LIGHTGBM_TPU_PREDICT_BUCKETS=0 for the exact-shape legacy path."""
        models = self._used_models(num_iteration)
        k = self.num_tree_per_iteration
        n = data.shape[0]
        if not models:
            return np.zeros((k, n))
        import os

        if os.environ.get("LIGHTGBM_TPU_PREDICT_BUCKETS", "1") == "0":
            return self._predict_raw_scores_unbucketed(data, models, k)
        from ..ops.qpredict import quant_predict_enabled

        linear = any(getattr(t, "is_linear", False) for t in models)
        key = (len(models), k, linear)
        if linear:
            # v3 linear-leaf serving path (serve/compilecache.py): the
            # same bucket ladder, one extra coefficient gather per tree
            if quant_predict_enabled():
                Log.warning(
                    "LIGHTGBM_TPU_QUANT_PREDICT=1 ignored: quantized "
                    "serving does not support linear-leaf models; "
                    "serving exact")
            cached = getattr(self, "_bucketed_predictor", None)
            if cached is None or cached[0] != key:
                from ..serve.compilecache import BucketedLinearRawPredictor

                cached = (key,
                          BucketedLinearRawPredictor.from_models(models, k))
                self._bucketed_predictor = cached
            return cached[1].predict_raw_scores(np.asarray(data, np.float64))
        if quant_predict_enabled():
            # LIGHTGBM_TPU_QUANT_PREDICT=1: int16 rank-quantized
            # traversal (ops/qpredict.py) — route decisions are exact,
            # leaf values narrow to f16 (drift_bound documents the
            # output bound); unset/0 keeps the bit-exact default
            cached = getattr(self, "_quantized_predictor", None)
            if cached is None or cached[0] != key:
                from ..ops.qpredict import quantize_tree_arrays
                from ..serve.artifact import stacked_tree_arrays
                from ..serve.compilecache import BucketedQuantizedPredictor

                q = quantize_tree_arrays(
                    stacked_tree_arrays(models),
                    num_features=int(self.max_feature_idx) + 1)
                cached = (key, BucketedQuantizedPredictor.from_qtree_arrays(q, k))
                self._quantized_predictor = cached
            return cached[1].predict_raw_scores(np.asarray(data, np.float64))
        cached = getattr(self, "_bucketed_predictor", None)
        if cached is None or cached[0] != key:
            from ..serve.compilecache import BucketedRawPredictor

            cached = (key, BucketedRawPredictor.from_models(models, k))
            self._bucketed_predictor = cached
        return cached[1].predict_raw_scores(np.asarray(data, np.float64))

    def _predict_raw_scores_unbucketed(self, data: np.ndarray, models, k) -> np.ndarray:
        n = data.shape[0]
        from ..model.ensemble import split_hi_lo

        hi, lo, lo2 = split_hi_lo(np.asarray(data, np.float64))
        data_hi = jnp.asarray(hi)
        data_lo = jnp.asarray(lo)
        data_lo2 = jnp.asarray(lo2)
        arrays = stack_trees(models)
        linear = "leaf_feat_real" in arrays
        if linear:
            from ..ops.predict import predict_raw_linear
        out = np.zeros((k, n))
        for kk in range(k):
            idx = np.asarray([i for i in range(len(models)) if i % k == kk])
            raw_args = (
                data_hi,
                data_lo,
                data_lo2,
                arrays["split_feature"][idx],
                arrays["threshold_real"][idx],
                arrays["threshold_real_lo"][idx],
                arrays["threshold_real_lo2"][idx],
                arrays["default_value"][idx],
                arrays["default_value_lo"][idx],
                arrays["default_value_lo2"][idx],
                arrays["is_categorical"][idx],
                arrays["left_child"][idx],
                arrays["right_child"][idx],
                arrays["leaf_value"][idx],
            )
            if linear:
                scores = predict_raw_linear(
                    *raw_args,
                    arrays["leaf_feat_real"][idx],
                    arrays["leaf_feat_valid"][idx],
                    arrays["leaf_coeff"][idx],
                    arrays["leaf_const"][idx],
                    arrays["leaf_is_linear"][idx],
                )
            else:
                scores = predict_raw(*raw_args)
            out[kk] = np.asarray(scores, np.float64)
        return out

    def predict(self, data: np.ndarray, num_iteration: int = -1,
                raw_score: bool = False, pred_leaf: bool = False) -> np.ndarray:
        """Booster-level predict: (N,) or (N, K) converted outputs."""
        if pred_leaf:
            models = self._used_models(num_iteration)
            out = np.stack([t.predict_leaf_index(np.asarray(data, np.float64))
                            for t in models], axis=1)
            return out
        if self.config is not None and getattr(self.config, "pred_early_stop", False):
            # margin-based per-row early exit over trees
            # (CreatePredictionEarlyStopInstance, prediction_early_stop.cpp:74-89;
            # Predictor ctor wiring, application/predictor.hpp:24-120)
            from .pred_early_stop import (
                create_prediction_early_stop_instance,
                predict_with_early_stop,
            )

            # binary margin only applies to sigmoid-type objectives; the
            # reference keeps "none" (never stop) otherwise (predictor.hpp)
            if self.num_tree_per_iteration > 1:
                es_type = "multiclass"
            elif self.objective is not None and self.objective.name == "binary":
                es_type = "binary"
            else:
                es_type = "none"
            inst = create_prediction_early_stop_instance(
                es_type,
                int(self.config.pred_early_stop_freq),
                float(self.config.pred_early_stop_margin),
            )
            raw = predict_with_early_stop(
                self, np.asarray(data, np.float64), inst, num_iteration
            ).T  # (K, N)
            if raw_score:
                return raw[0] if raw.shape[0] == 1 else raw.T
            conv = self._convert_output(raw)
            return conv[0] if conv.shape[0] == 1 else conv.T
        raw = self.predict_raw_scores(data, num_iteration)
        if raw_score:
            return raw[0] if raw.shape[0] == 1 else raw.T
        conv = self._convert_output(raw)
        return conv[0] if conv.shape[0] == 1 else conv.T

    def _convert_output(self, raw: np.ndarray) -> np.ndarray:
        """Objective output conversion on (K, N) raw scores.  Like the
        traversal, the conversion's jnp programs are shape-keyed, so it
        runs bucket-padded (serve/compilecache.convert_bucketed) unless
        LIGHTGBM_TPU_PREDICT_BUCKETS=0 pins the exact-shape path."""
        if self.objective is None:
            return raw
        import os

        if os.environ.get("LIGHTGBM_TPU_PREDICT_BUCKETS", "1") == "0":
            return np.asarray(
                self.objective.convert_output(jnp.asarray(raw)), np.float64
            )
        from ..serve.compilecache import convert_bucketed

        return convert_bucketed(raw, self.objective.convert_output)

    # ------------------------------------------------------------------
    def sub_model_name(self) -> str:
        return "tree"

    def save_model_to_string(self, num_iteration: int = -1) -> str:
        """GBDT::SaveModelToString (gbdt.cpp:854-898) — reference format."""
        parts = [self.sub_model_name()]
        parts.append(f"num_class={self.num_class}")
        parts.append(f"num_tree_per_iteration={self.num_tree_per_iteration}")
        parts.append(f"label_index={self.label_idx}")
        parts.append(f"max_feature_idx={self.max_feature_idx}")
        if self.objective is not None:
            parts.append(f"objective={self.objective.to_string()}")
        if self.boost_from_average_:
            parts.append("boost_from_average")
        parts.append("feature_names=" + " ".join(self.feature_names))
        if self.train_set is not None:
            parts.append("feature_infos=" + " ".join(self.train_set.feature_infos()))
        parts.append("")
        for i, tree in enumerate(self._used_models(num_iteration)):
            parts.append(f"Tree={i}")
            parts.append(tree.to_string())
        parts.append("")
        parts.append("feature importances:")
        for name, cnt in self.feature_importance_pairs():
            parts.append(f"{name}={cnt}")
        return "\n".join(parts) + "\n"

    def save_model_to_file(self, filename: str, num_iteration: int = -1) -> None:
        with open(filename, "w") as f:
            f.write(self.save_model_to_string(num_iteration))

    def load_model_from_string(self, model_str: str) -> None:
        """GBDT::LoadModelFromString (gbdt.cpp:912-1008)."""
        self.models = []
        header, _, rest = model_str.partition("Tree=")
        kv = {}
        for line in header.splitlines():
            if "=" in line:
                k, _, v = line.partition("=")
                kv[k.strip()] = v.strip()
        if "num_class" not in kv:
            Log.fatal("Model file doesn't specify the number of classes")
        self.num_class = int(kv["num_class"])
        self.num_tree_per_iteration = int(
            kv.get("num_tree_per_iteration", self.num_class)
        )
        if "label_index" not in kv:
            Log.fatal("Model file doesn't specify the label index")
        self.label_idx = int(kv["label_index"])
        if "max_feature_idx" not in kv:
            Log.fatal("Model file doesn't specify max_feature_idx")
        self.max_feature_idx = int(kv["max_feature_idx"])
        self.boost_from_average_ = "boost_from_average" in header.splitlines()
        self.objective_name_loaded = kv.get("objective", "")
        self.feature_names = kv.get("feature_names", "").split()
        # tree blocks
        if rest:
            blocks = ("Tree=" + rest).split("Tree=")
            for blk in blocks:
                blk = blk.strip()
                if not blk or blk.startswith("feature importances"):
                    continue
                body = blk.partition("\n")[2]
                body = body.split("\nfeature importances:")[0]
                self.models.append(Tree.from_string(body))
        self.num_init_iteration = len(self.models) // max(self.num_tree_per_iteration, 1)
        self.iter = 0

    def feature_importance_pairs(self):
        """Split-count importance (GBDT::FeatureImportance,
        gbdt.cpp:1010-1034), sorted descending, nonzero only."""
        imp = np.zeros(self.max_feature_idx + 1, np.int64)
        for tree in self.models:
            m = tree.num_leaves - 1
            for s in range(m):
                if tree.split_gain[s] > 0:
                    imp[tree.split_feature[s]] += 1
        names = self.feature_names or [
            f"Column_{i}" for i in range(self.max_feature_idx + 1)
        ]
        pairs = [(names[i], int(imp[i])) for i in range(len(imp)) if imp[i] > 0]
        pairs.sort(key=lambda p: -p[1])
        return pairs

    def feature_importance(self, importance_type: str = "split") -> np.ndarray:
        imp = np.zeros(self.max_feature_idx + 1, np.float64)
        for tree in self.models:
            m = tree.num_leaves - 1
            for s in range(m):
                if tree.split_gain[s] > 0:
                    if importance_type == "gain":
                        imp[tree.split_feature[s]] += tree.split_gain[s]
                    else:
                        imp[tree.split_feature[s]] += 1
        return imp
