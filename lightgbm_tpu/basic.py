"""User-facing Dataset and Booster — counterpart of
python-package/lightgbm/basic.py (Dataset:551, Booster:1176).

The reference's classes are ctypes shims over the C API; here they wrap the
in-process host/device pipeline directly: Dataset lazily constructs a
BinnedDataset (io/dataset.py), Booster owns a boosting driver
(boosting/gbdt.py) with device-resident state.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from .boosting import create_boosting
from .config import Config
from .io.dataset import BinnedDataset
from .io.sparse import is_sparse, map_dense_blocks
from .metric import create_metric
from .objective import create_objective
from .obs import tracer
from .utils.log import Log


def _to_2d_float(data, want_cats: bool = False):
    """-> (array, column_names) or, with ``want_cats``, (array, names,
    auto_categorical_indices).  Pandas ``category`` dtype columns are
    mapped to their integer codes (missing -> NaN) and reported as
    auto-detected categorical features, mirroring the reference's pandas
    handling under categorical_feature="auto"
    (python-package/lightgbm/basic.py _data_from_pandas)."""
    try:
        import pandas as pd

        if isinstance(data, pd.DataFrame):
            cat_idx = [i for i, c in enumerate(data.columns)
                       if isinstance(data.dtypes.iloc[i], pd.CategoricalDtype)]
            levels = []
            if cat_idx:
                data = data.copy(deep=False)
                for i in cat_idx:
                    col = data.columns[i]
                    levels.append(list(data[col].cat.categories))
                    codes = data[col].cat.codes.to_numpy(np.float64)
                    codes[codes < 0] = np.nan  # code -1 == missing
                    data[col] = codes
            arr = data.to_numpy(dtype=np.float64)
            names = [str(c) for c in data.columns]
            return (arr, names, cat_idx, levels) if want_cats else (arr, names)
    except ImportError:
        pass
    # scipy CSR/CSC input (basic.py __init_from_csr/__init_from_csc) stays
    # as it is: Dataset bins and bundles it through io/sparse.py and
    # Booster.predict walks it in row blocks, so neither densifies the
    # table.  What the device then holds is dense (README sparse-bins
    # decision): the bundled matrix.
    if is_sparse(data):
        return (data, None, [], []) if want_cats else (data, None)
    arr = np.asarray(data, dtype=np.float64)
    if arr.ndim == 1:
        arr = arr.reshape(-1, 1)
    return (arr, None, [], []) if want_cats else (arr, None)


def _map_pandas_categorical(data, pandas_categorical):
    """Predict-time DataFrame: map category columns through the TRAINING
    category order (reference basic.py _data_from_pandas +
    pandas_categorical round-trip) so codes line up with the model."""
    try:
        import pandas as pd
    except ImportError:  # pragma: no cover
        return data
    if not isinstance(data, pd.DataFrame) or not pandas_categorical:
        return data
    cat_cols = [c for i, c in enumerate(data.columns)
                if isinstance(data.dtypes.iloc[i], pd.CategoricalDtype)]
    if not cat_cols:
        return data
    if len(cat_cols) != len(pandas_categorical):
        # the reference raises on exactly this shape mismatch
        # ("train and valid dataset categorical_feature do not match")
        Log.fatal(
            "predict data has %d pandas categorical columns but the model "
            "was trained with %d", len(cat_cols), len(pandas_categorical),
        )
    data = data.copy(deep=False)
    for col, levels in zip(cat_cols, pandas_categorical):
        codes = pd.Categorical(data[col], categories=levels).codes.astype(np.float64)
        codes[codes < 0] = np.nan
        data[col] = codes
    return data


class Dataset:
    """Lazily-constructed binned dataset (basic.py:551 Dataset)."""

    def __init__(
        self,
        data,
        label=None,
        max_bin: Optional[int] = None,
        reference: Optional["Dataset"] = None,
        weight=None,
        group=None,
        init_score=None,
        silent: bool = False,
        feature_name="auto",
        categorical_feature="auto",
        params: Optional[Dict[str, Any]] = None,
        free_raw_data: bool = False,
    ):
        if isinstance(data, str):
            self.data_path = data
            self.data = None
            self.pandas_columns = None
            self._auto_categorical = []
            self.pandas_categorical = []
        else:
            self.data_path = None
            (self.data, self.pandas_columns, self._auto_categorical,
             self.pandas_categorical) = _to_2d_float(data, want_cats=True)
        self.label = label
        self.max_bin = max_bin
        self.reference = reference
        self.weight = weight
        self.group = group
        self.init_score = init_score
        self.params = dict(params) if params else {}
        # only an EXPLICIT max_bin argument becomes a dataset param —
        # otherwise booster params may fill it at Booster construction
        if max_bin is not None:
            self.params.setdefault("max_bin", max_bin)
        self.feature_name = feature_name
        self.categorical_feature = categorical_feature
        self.free_raw_data = free_raw_data
        self._constructed: Optional[BinnedDataset] = None
        self.label_idx = 0

    # ------------------------------------------------------------------
    def construct(self, extra_params: Optional[Dict[str, Any]] = None) -> BinnedDataset:
        """Build (or return) the binned dataset (basic.py _lazy_init).

        ``extra_params`` fill gaps for this construction only (booster
        params reaching the dataset) — the Dataset's own ``params`` win
        and are never mutated, so the same un-constructed Dataset can be
        reused by a second Booster with different params.
        """
        if self._constructed is not None:
            return self._constructed
        # a real construction only: once a Dataset (obs/trace.py's rule)
        with tracer.stage("dataset_construct") as stage:
            ds, source = self._construct(extra_params)
            stage.attrs.update(rows=ds.num_data, features=ds.num_features, source=source)
        return ds

    def _construct(self, extra_params: Optional[Dict[str, Any]]):
        """``(the binned dataset, where it came from)``: ``binary`` (the
        program's own dataset cache), ``file`` (text, parsed or streamed),
        ``sparse`` (scipy CSR/CSC) or ``matrix``."""
        merged = dict(extra_params) if extra_params else {}
        merged.update(self.params)
        cfg = Config.from_params(
            {k: v for k, v in merged.items() if k != "categorical_feature"}
        )
        if self.data is None and self.data_path is not None:
            # binary dataset cache first (DatasetLoader::LoadFromBinFile)
            if BinnedDataset.is_binary_cache(self.data_path):
                ds = BinnedDataset.load_binary(self.data_path)
                if self.label is not None:
                    ds.metadata.set_label(self.label)
                if self.weight is not None:
                    ds.metadata.set_weights(self.weight)
                if self.group is not None:
                    ds.metadata.set_query(self.group)
                if self.init_score is not None:
                    ds.metadata.set_init_score(self.init_score)
                self._constructed = ds
                return ds, "binary"
            from .data.ingest import should_stream, stream_dataset

            if should_stream(self.data_path, cfg):
                # out-of-core path (data/ingest.py): two-pass chunked
                # construction, bit-identical mappers/bins to the
                # in-memory load of the same file — the raw float matrix
                # is never materialized, so self.data stays None
                ref = self.reference.construct() if self.reference is not None else None
                ds = stream_dataset(
                    self.data_path, cfg,
                    feature_name=self.feature_name,
                    categorical_feature=self.categorical_feature,
                    reference=ref,
                )
                if self.label is not None:
                    ds.metadata.set_label(self.label)
                if self.weight is not None:
                    ds.metadata.set_weights(self.weight)
                if self.group is not None:
                    ds.metadata.set_query(self.group)
                if self.init_score is not None:
                    ds.metadata.set_init_score(self.init_score)
                self.label_idx = ds.label_idx
                self._constructed = ds
                return ds, "file"
            from .io.parser import load_text_file

            feats, label, weights, group, names, label_idx = load_text_file(
                self.data_path, cfg
            )
            self.data = feats
            self.label_idx = label_idx
            if self.label is None:
                self.label = label
            if self.weight is None:
                self.weight = weights
            if self.group is None:
                self.group = group
            if self.feature_name == "auto":
                self.feature_name = names

        names = None
        if self.feature_name != "auto" and self.feature_name is not None:
            names = list(self.feature_name)
        elif self.pandas_columns is not None:
            names = self.pandas_columns

        cats: Optional[Sequence[int]] = None
        if self.categorical_feature != "auto" and self.categorical_feature:
            cats = []
            for c in self.categorical_feature:
                if isinstance(c, str):
                    if names and c in names:
                        cats.append(names.index(c))
                    else:
                        Log.fatal("Unknown categorical feature %s", c)
                else:
                    cats.append(int(c))
        elif self.categorical_feature == "auto" and getattr(
            self, "_auto_categorical", None
        ):
            # pandas category dtype columns (mapped to codes in
            # _to_2d_float) become categorical features automatically
            cats = list(self._auto_categorical)

        ref = self.reference.construct() if self.reference is not None else None
        if self.reference is not None:
            self._remap_categorical_to_reference(self.reference)
        sparse = is_sparse(self.data)
        source = "file" if self.data_path is not None else "sparse" if sparse else "matrix"
        make = BinnedDataset.from_sparse if sparse else BinnedDataset.from_raw
        self._constructed = make(
            self.data,
            cfg,
            label=self.label,
            weight=self.weight,
            group=self.group,
            init_score=self.init_score,
            feature_names=names,
            categorical_features=cats,
            reference=ref,
        )
        self._constructed.label_idx = self.label_idx
        if self.free_raw_data:
            self.data = None
        return self._constructed, source

    # ------------------------------------------------------------------
    def _remap_categorical_to_reference(self, ref: "Dataset") -> None:
        """Validation Dataset built from a pandas frame: its category
        columns were coded against the frame's OWN level order
        (_to_2d_float), but the tree thresholds are bin ids over the
        TRAINING set's levels — remap codes through the reference's
        ``pandas_categorical`` (the reference's _data_from_pandas
        round-trip) and, like the reference, raise when the categorical
        column sets don't line up."""
        train_levels = getattr(ref, "pandas_categorical", None) or []
        my_levels = getattr(self, "pandas_categorical", None) or []
        if not my_levels and not train_levels:
            return
        if len(my_levels) != len(train_levels):
            Log.fatal(
                "train and valid dataset categorical_feature do not match: "
                "valid has %d pandas categorical columns, train has %d",
                len(my_levels), len(train_levels),
            )
        if self.data is None:
            return
        for col_idx, vl, tl in zip(self._auto_categorical, my_levels,
                                   train_levels):
            if list(vl) == list(tl):
                continue
            # valid-code -> train-code lookup; levels unseen at train
            # time become missing (NaN), matching predict-time remap
            pos = {v: i for i, v in enumerate(tl)}
            lut = np.asarray([pos.get(v, np.nan) for v in vl], np.float64)
            col = np.asarray(self.data[:, col_idx], np.float64)
            ok = ~np.isnan(col)
            out = np.full(col.shape, np.nan)
            out[ok] = lut[col[ok].astype(np.int64)]
            self.data[:, col_idx] = out
        self.pandas_categorical = [list(t) for t in train_levels]

    def create_valid(self, data, label=None, weight=None, group=None,
                     init_score=None, silent=False, params=None) -> "Dataset":
        return Dataset(
            data,
            label=label,
            reference=self,
            weight=weight,
            group=group,
            init_score=init_score,
            silent=silent,
            params=params or self.params,
        )

    def set_label(self, label) -> "Dataset":
        self.label = label
        if self._constructed is not None:
            self._constructed.metadata.set_label(label)
        return self

    def set_weight(self, weight) -> "Dataset":
        self.weight = weight
        if self._constructed is not None:
            self._constructed.metadata.set_weights(weight)
        return self

    def set_group(self, group) -> "Dataset":
        self.group = group
        if self._constructed is not None:
            self._constructed.metadata.set_query(group)
        return self

    def set_init_score(self, init_score) -> "Dataset":
        self.init_score = init_score
        if self._constructed is not None:
            self._constructed.metadata.set_init_score(init_score)
        return self

    def get_label(self):
        if self._constructed is not None:
            return np.asarray(self._constructed.metadata.label)
        return None if self.label is None else np.asarray(self.label)

    def get_weight(self):
        if self._constructed is not None and self._constructed.metadata.weights is not None:
            return np.asarray(self._constructed.metadata.weights)
        return None if self.weight is None else np.asarray(self.weight)

    def get_group(self):
        return None if self.group is None else np.asarray(self.group)

    def get_init_score(self):
        return None if self.init_score is None else np.asarray(self.init_score)

    def num_data(self) -> int:
        if self._constructed is not None:
            return self._constructed.num_data
        return self.data.shape[0] if self.data is not None else 0

    def num_feature(self) -> int:
        if self._constructed is not None:
            return self._constructed.num_total_features
        return self.data.shape[1] if self.data is not None else 0

    def save_binary(self, filename: str) -> "Dataset":
        # record which source file the cache came from, so a later load
        # can refuse the cache when that file changes underneath it
        self.construct().save_binary(filename, source_path=self.data_path)
        return self

    def subset(self, used_indices, params=None) -> "Dataset":
        """Row-subset Dataset sharing this dataset's bin mappers and
        BINNED rows (Dataset::CopySubset — no per-fold re-binning)."""
        used_indices = np.asarray(used_indices)
        sub = Dataset.__new__(Dataset)
        sub.data_path = None
        sub.data = self.data[used_indices] if self.data is not None else None
        sub.pandas_columns = self.pandas_columns
        sub._auto_categorical = list(getattr(self, "_auto_categorical", []))
        sub.pandas_categorical = list(getattr(self, "pandas_categorical", []))
        sub.label = None
        sub.max_bin = self.max_bin
        sub.reference = self
        sub.weight = None
        sub.init_score = None
        sub.params = dict(params) if params else dict(self.params)
        sub.feature_name = self.feature_name
        sub.categorical_feature = self.categorical_feature
        sub.free_raw_data = False
        sub.label_idx = self.label_idx
        sub._constructed = self.construct().subset(used_indices)
        qb = sub._constructed.metadata.query_boundaries
        sub.group = None if qb is None else np.diff(qb)
        return sub


class Booster:
    """Training/prediction handle (basic.py:1176 Booster)."""

    def __init__(
        self,
        params: Optional[Dict[str, Any]] = None,
        train_set: Optional[Dataset] = None,
        model_file: Optional[str] = None,
        model_str: Optional[str] = None,
        silent: bool = False,
    ):
        self.params = dict(params) if params else {}
        self.best_iteration = -1
        self.best_score: Dict[str, Dict[str, float]] = {}
        self._name_to_index: Dict[str, int] = {}

        self.pandas_categorical = []
        if train_set is not None:
            # once a Booster, where lgb.train and lgb.Booster(...) both pass
            with tracer.stage("booster_init"):
                self.config = Config.from_params(self.params)
                self.pandas_categorical = getattr(train_set, "pandas_categorical", [])
                # dataset-relevant train params reach construction unless the
                # Dataset set them explicitly (Dataset._update_params: the
                # dataset's own params win, booster params fill the gaps) —
                # passed per-construction, never written into train_set.params
                binned = train_set.construct(extra_params=self.params)
                self.train_dataset = train_set
                self.objective = create_objective(self.config)
                self.boosting = create_boosting(self.config.boosting_type)
                # training metrics only when asked (is_provide_training_metric
                # gate, gbdt.cpp ResetTrainingData); the python engine path
                # evaluates "training" as a valid set instead
                training_metrics = (
                    self._make_metrics(binned) if self.config.is_training_metric else []
                )
                self.boosting.init(self.config, binned, self.objective, training_metrics)
            self._num_datasets = 1
        elif model_file is not None or model_str is not None:
            if model_file is not None:
                with open(model_file) as f:
                    model_str = f.read()
            model_str = self._strip_pandas_categorical(model_str)
            self.config = Config.from_params(self.params)
            self.boosting = create_boosting("gbdt")
            self.boosting.config = self.config
            self.boosting.load_model_from_string(model_str)
            self.objective = self._objective_from_model_string(
                self.boosting.objective_name_loaded
            )
            self.boosting.objective = self.objective
            self.train_dataset = None
            self._num_datasets = 0
        else:
            Log.fatal("Booster needs a train_set, model_file or model_str")

    # ------------------------------------------------------------------
    def _strip_pandas_categorical(self, model_str: str) -> str:
        """Parse + remove the trailing pandas_categorical json line
        (written by model_to_string; reference model-file convention).
        The removal span comes from the RAW line — computing it from the
        stripped text mis-sliced model files with CRLF endings or
        trailing whitespace on the line."""
        marker = "\npandas_categorical:"
        pos = model_str.rfind(marker)
        if pos >= 0:
            import json

            raw_line, _, rest = model_str[pos + len(marker):].partition("\n")
            try:
                self.pandas_categorical = json.loads(raw_line.strip()) or []
            except ValueError:
                self.pandas_categorical = []
            model_str = model_str[:pos] + rest
        return model_str

    def _objective_from_model_string(self, obj_str: str):
        from .objective import objective_from_string

        return objective_from_string(obj_str)

    def _metric_names(self) -> List[str]:
        names = self.config.metric
        if not names:
            names = [self.config.objective]
        return [n for n in names if n.lower() not in ("none", "null", "")]

    def _make_metrics(self, binned):
        metrics = []
        for name in self._metric_names():
            m = create_metric(name, self.config)
            if m is None:
                Log.warning("Unknown metric %s", name)
                continue
            m.init(binned.metadata, binned.num_data)
            metrics.append(m)
        return metrics

    # ------------------------------------------------------------------
    def add_valid(self, data: Dataset, name: str) -> "Booster":
        binned = data.construct()
        self.boosting.add_valid(binned, self._make_metrics(binned), name)
        self._name_to_index[name] = self._num_datasets
        self._num_datasets += 1
        return self

    def update(self, train_set: Optional[Dataset] = None, fobj=None) -> bool:
        """One boosting iteration (Booster.update, basic.py:1377).  With a
        custom ``fobj(preds, train_set) -> (grad, hess)`` mirrors
        LGBM_BoosterUpdateOneIterCustom."""
        if fobj is None:
            return self.boosting.train_one_iter(is_eval=False)
        preds = self._raw_train_scores()
        grad, hess = fobj(preds, self.train_dataset)
        return self.boosting.train_one_iter(
            np.asarray(grad, np.float32),
            np.asarray(hess, np.float32),
            is_eval=False,
        )

    def _raw_train_scores(self) -> np.ndarray:
        sc = self.boosting._train_score_host()
        return sc[0] if sc.shape[0] == 1 else sc.reshape(-1)

    def rollback_one_iter(self) -> "Booster":
        self.boosting.rollback_one_iter()
        return self

    def current_iteration(self) -> int:
        return self.boosting.current_iteration()

    @property
    def num_trees(self) -> int:
        return self.boosting.num_trees

    # ------------------------------------------------------------------
    def eval_train(self, feval=None):
        return self.__inner_eval("training", 0, feval)

    def eval_valid(self, feval=None):
        out = []
        for name, idx in self._name_to_index.items():
            out.extend(self.__inner_eval(name, idx, feval))
        return out

    def eval(self, data: Dataset, name: str, feval=None):
        if name in self._name_to_index:
            return self.__inner_eval(name, self._name_to_index[name], feval)
        Log.fatal("Dataset %s was not added with add_valid", name)

    def __inner_eval(self, data_name: str, data_idx: int, feval=None):
        """[(data_name, metric_name, value, bigger_is_better), ...]"""
        results = []
        for name, val, bigger in self.boosting.get_eval_at(data_idx):
            results.append((data_name, name, val, bigger))
        if feval is not None:
            if data_idx == 0:
                preds = self._raw_train_scores()
                fdata = self.train_dataset
            else:
                sc = self.boosting._valid_score_host(data_idx - 1)
                preds = sc[0] if sc.shape[0] == 1 else sc.reshape(-1)
                binned = self.boosting.valid_sets[data_idx - 1]
                fdata = Dataset.__new__(Dataset)
                fdata._constructed = binned
                fdata.label = np.asarray(binned.metadata.label)
                qb = binned.metadata.query_boundaries
                fdata.group = None if qb is None else np.diff(qb)
                fdata.weight = binned.metadata.weights
                fdata.init_score = None
            ret = feval(preds, fdata)
            if isinstance(ret, tuple):
                ret = [ret]
            for name, val, bigger in ret:
                results.append((data_name, name, val, bigger))
        return results

    # ------------------------------------------------------------------
    def predict(
        self,
        data,
        num_iteration: int = -1,
        raw_score: bool = False,
        pred_leaf: bool = False,
        data_has_header: bool = False,
        is_reshape: bool = True,
    ) -> np.ndarray:
        if isinstance(data, str):
            from .io.parser import load_text_file

            feats, _, _, _, _, _ = load_text_file(data, self.config)
            data = feats
        else:
            data = _map_pandas_categorical(data, self.pandas_categorical)
            data, _ = _to_2d_float(data)
        if is_sparse(data):
            # row blocks of one size: the table is never dense whole, and the
            # blocks share one compiled predictor
            return np.concatenate(map_dense_blocks(
                lambda block: self.boosting.predict(
                    block, num_iteration=num_iteration, raw_score=raw_score,
                    pred_leaf=pred_leaf), data))
        return self.boosting.predict(
            data, num_iteration=num_iteration, raw_score=raw_score, pred_leaf=pred_leaf
        )

    # ------------------------------------------------------------------
    def save_model(self, filename: str, num_iteration: int = -1) -> "Booster":
        with open(filename, "w") as f:
            f.write(self.model_to_string(num_iteration))
        return self

    def model_to_string(self, num_iteration: int = -1) -> str:
        s = self.boosting.save_model_to_string(num_iteration)
        if self.pandas_categorical:
            import json

            s += "\npandas_categorical:" + json.dumps(
                self.pandas_categorical, default=str
            ) + "\n"
        return s

    def dump_model(self, num_iteration: int = -1) -> dict:
        """JSON dump (GBDT::DumpModel, gbdt.cpp:702-736)."""
        b = self.boosting
        return {
            "name": b.sub_model_name(),
            "version": "v2",
            "num_class": b.num_class,
            "num_tree_per_iteration": b.num_tree_per_iteration,
            "label_index": b.label_idx,
            "max_feature_idx": b.max_feature_idx,
            "objective": b.objective.to_string() if b.objective else "",
            "feature_names": list(b.feature_names),
            "tree_info": [t.to_json() for t in b._used_models(num_iteration)],
        }

    def feature_importance(self, importance_type: str = "split") -> np.ndarray:
        return self.boosting.feature_importance(importance_type)

    def feature_name(self) -> List[str]:
        return list(self.boosting.feature_names)

    # pickling support: serialize via model string
    def __getstate__(self):
        return {
            "params": self.params,
            "model_str": self.model_to_string(),
            "best_iteration": self.best_iteration,
            "best_score": self.best_score,
        }

    def __setstate__(self, state):
        new = Booster(params=state["params"], model_str=state["model_str"])
        self.__dict__.update(new.__dict__)
        self.best_iteration = state["best_iteration"]
        self.best_score = state["best_score"]

    def __copy__(self):
        return self.__deepcopy__(None)

    def __deepcopy__(self, memo):
        return Booster(params=self.params, model_str=self.model_to_string())
