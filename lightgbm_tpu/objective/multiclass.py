"""Multiclass objectives — parity with
src/objective/multiclass_objective.hpp (softmax:16-136, OVA:139-225).

Score layout is ``(K, N)`` — the reference's flat ``num_data*k + i``
indexing reshaped; the softmax runs across the class axis on device.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.log import Log
from .base import ObjectiveFunction
from .binary import BinaryLogloss


class MulticlassSoftmax(ObjectiveFunction):
    name = "multiclass"
    # per-class row-local gradients (gradients_rowwise_class): the fused
    # partitioned trainer can drive K trees/iteration from the packed
    # matrix's K score channels (GBDT per-class loop, gbdt.cpp:445-480)
    rowwise_multi = True

    def __init__(self, config):
        self.num_class = int(config.num_class)

    def init(self, metadata, num_data: int) -> None:
        super().init(metadata, num_data)
        lab = np.asarray(metadata.label, np.int32)
        if lab.size and (lab.min() < 0 or lab.max() >= self.num_class):
            Log.fatal(
                "Label must be in [0, %d), but found %d in label",
                self.num_class,
                int(lab.min() if lab.min() < 0 else lab.max()),
            )
        self.onehot = jnp.asarray(
            (lab[None, :] == np.arange(self.num_class, dtype=np.int32)[:, None]).astype(
                np.float32
            )
        )  # (K, N)

    def get_gradients(self, score):
        # (K, N): softmax over classes; grad = p - 1[y=k]; hess = 2p(1-p)
        p = jnp.exp(score - jnp.max(score, axis=0, keepdims=True))
        p = p / jnp.sum(p, axis=0, keepdims=True)
        grad = p - self.onehot
        hess = 2.0 * p * (1.0 - p)
        if self.weights is not None:
            grad = grad * self.weights[None, :]
            hess = hess * self.weights[None, :]
        return grad, hess

    def gradients_rowwise_all(self, scores, label, weight):
        """All K gradient planes from the score rows in ARBITRARY row
        order (the partitioned trainer's channels): scores (K, n), label
        the raw class index; returns ((K, n), (K, n))."""
        p = jnp.exp(scores - jnp.max(scores, axis=0, keepdims=True))
        p = p / jnp.sum(p, axis=0, keepdims=True)
        # runs inside the fused update kernel: Mosaic's iota is integer and
        # at least 2-D (a 1-D float arange is refused: "'tpu.iota' op
        # result must be vector of integer")
        classes = jax.lax.broadcasted_iota(
            jnp.int32, (self.num_class, 1), 0).astype(jnp.float32)
        onehot = (label.reshape(1, -1) == classes).astype(jnp.float32)
        onehot = onehot.reshape(p.shape)
        grad = p - onehot
        hess = 2.0 * p * (1.0 - p)
        if weight is not None:
            grad = grad * weight
            hess = hess * weight
        return grad, hess

    def convert_output(self, score):
        p = jnp.exp(score - jnp.max(score, axis=0, keepdims=True))
        return p / jnp.sum(p, axis=0, keepdims=True)

    @property
    def num_tree_per_iteration(self) -> int:
        return self.num_class

    @property
    def num_predict_one_row(self) -> int:
        return self.num_class

    def to_string(self) -> str:
        return f"{self.name} num_class:{self.num_class}"


class MulticlassOVA(ObjectiveFunction):
    """K independent BinaryLogloss objectives
    (multiclass_objective.hpp:139-225)."""

    name = "multiclassova"
    rowwise_multi = True

    def __init__(self, config):
        self.num_class = int(config.num_class)
        self.sigmoid = float(config.sigmoid)
        self._config = config

    def init(self, metadata, num_data: int) -> None:
        super().init(metadata, num_data)
        self.binary = []
        for k in range(self.num_class):
            b = BinaryLogloss(self._config, is_pos=lambda lab, kk=k: lab == kk)
            b.init(metadata, num_data)
            self.binary.append(b)

    def get_gradients(self, score):
        outs = [self.binary[k].get_gradients(score[k]) for k in range(self.num_class)]
        grad = jnp.stack([g for g, _ in outs])
        hess = jnp.stack([h for _, h in outs])
        return grad, hess

    def gradients_rowwise_all(self, scores, label, weight):
        # the raw class-index label goes through: binary[k]'s is_pos
        # closure tests ``label == k`` itself
        outs = [
            self.binary[k].gradients_rowwise(scores[k : k + 1], label, weight)
            for k in range(self.num_class)
        ]
        grad = jnp.concatenate([g for g, _ in outs], axis=0)
        hess = jnp.concatenate([h for _, h in outs], axis=0)
        return grad, hess

    def convert_output(self, score):
        return 1.0 / (1.0 + jnp.exp(-self.sigmoid * score))

    @property
    def num_tree_per_iteration(self) -> int:
        return self.num_class

    @property
    def num_predict_one_row(self) -> int:
        return self.num_class

    def to_string(self) -> str:
        return f"{self.name} num_class:{self.num_class} sigmoid:{self.sigmoid:g}"
