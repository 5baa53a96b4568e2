"""Sparse linear learner (logistic regression) over CSR batches.

No reference counterpart (dmlc-core has no models); this is the canonical
TPU consumer of the framework's data layout:

- single-chip: flat padded CSR (parallel.pad_to_bucket) + segmented-scan SpMV
- multi-chip: global [D, ...] batches (parallel.make_global_batch) under
  shard_map over the mesh's data axis; gradients of replicated params are
  psum-reduced by construction. Parallelism is DATA parallelism — the only
  axis the reference's world has (SURVEY.md §2.4: no TP/PP/SP/EP exists
  to mirror; data sharding IS dmlc-core's distributed model).

Padded rows carry weight 0, so they are loss- and gradient-neutral. The
SGD/shard_map scaffolding lives ONCE in models.common.SparseModelBase
(shared with the FM/FFM/ranking models — review r4).
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from dmlc_tpu.models.common import SparseModelBase, stable_bce_on_logits
from dmlc_tpu.ops.csr import segment_spmv

__all__ = ["SparseLinearModel"]


class SparseLinearModel(SparseModelBase):
    """Logistic regression on sparse CSR batches.

    Labels are mapped to {0, 1} via (label > 0) — accepts the ±1
    convention of libsvm files.
    """

    def __init__(self, num_features: int, l2: float = 0.0,
                 learning_rate: float = 0.1):
        self.num_features = num_features
        self.l2 = l2
        self.learning_rate = learning_rate

    def init_params(self, seed: int = 0) -> Dict[str, jnp.ndarray]:
        del seed  # linear model: zero init is canonical
        return {"w": jnp.zeros((self.num_features,), jnp.float32),
                "b": jnp.zeros((), jnp.float32)}

    def forward(self, params: Dict[str, Any],
                batch: Dict[str, Any]) -> jnp.ndarray:
        """Margins for one flat padded CSR batch."""
        num_rows = batch["label"].shape[0]
        margins = segment_spmv(batch["offset"], batch["index"],
                               batch["value"], params["w"],
                               num_rows=num_rows)
        return margins + params["b"]

    def _block_objective(self, params, flat, num_rows: int):
        del num_rows  # forward derives it from flat["label"]
        per_row = stable_bce_on_logits(self.forward(params, flat),
                                       flat["label"])
        w = flat["weight"]
        return jnp.sum(per_row * w), jnp.sum(w)

    # -- inference helpers

    def predict_proba(self, params, batch) -> jnp.ndarray:
        return jax.nn.sigmoid(self.forward(params, batch))
