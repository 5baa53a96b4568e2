"""Shared model math and scaffolding: one definition per formula — and
one definition of the SGD/shard_map training scaffolding — used by
every model and by both the single-chip and shard_map paths (so the
copies can never silently diverge)."""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["SparseModelBase", "stable_bce_on_logits"]


def _weighted_mean(lsum: jnp.ndarray, wsum: jnp.ndarray) -> jnp.ndarray:
    """lsum / wsum with a grad-safe guard for wsum == 0 (an all-padded
    block: lsum is 0 there too, so 0/1 = 0). NOT max(wsum, 1): clamping
    to 1 silently rescales the loss whenever 0 < wsum < 1 — a realistic
    regime for pair weights, which are PRODUCTS of sub-unit instance
    weights (review r4)."""
    denom = jnp.where(wsum > 0, wsum, 1.0)
    return lsum / denom


def stable_bce_on_logits(margins: jnp.ndarray,
                         labels: jnp.ndarray) -> jnp.ndarray:
    """Per-row binary cross-entropy on logits, numerically stable.

    Labels may follow the ±1 (libsvm) or {0,1} convention: y = label > 0.
    softplus is max(m, 0) + log1p(exp(-|m|)) with the true derivative,
    sigmoid(m), at m == 0 too — written out, the max/abs subgradients
    give 0 there, which is every row's margin at zero init.
    """
    y = (labels > 0).astype(jnp.float32)
    return jax.nn.softplus(margins) - margins * y


class SparseModelBase:
    """The ONE copy of the weighted-objective SGD scaffolding (review
    r4 — FM, FFM, and the ranking model each used to carry their own).

    Subclasses provide ``init_params``, ``_BATCH_KEYS`` (the batch
    columns their objective consumes beyond label/weight), and
    ``_block_objective(params, flat_batch, num_rows) -> (loss_sum,
    weight_sum)``. The base defines: the normalized weighted loss with
    optional l2 (over every param leaf except the bias "b"), the jitted
    SGD step, and the shard_map global loss (batch columns sharded on
    the data axis, params replicated, the two sums psum'd before
    normalizing — so the global mean weights every datum once, not
    every shard)."""

    _BATCH_KEYS: tuple = ("offset", "index", "value")
    l2: float = 0.0
    learning_rate: float = 0.1

    def _block_objective(self, params: Dict[str, Any],
                         flat: Dict[str, Any],
                         num_rows: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
        raise NotImplementedError

    def _l2_term(self, params: Dict[str, Any]) -> jnp.ndarray:
        return sum(jnp.sum(v ** 2) for k, v in params.items() if k != "b")

    def _check_columns(self, batch: Dict[str, Any]) -> None:
        """Named error for a batch missing columns this model's
        objective consumes (e.g. a qid-less source feeding the ranking
        model) — instead of a bare KeyError deep in a jit trace."""
        from dmlc_tpu.utils.logging import check
        missing = [k for k in self._BATCH_KEYS + ("label", "weight")
                   if k not in batch]
        check(not missing,
              f"{type(self).__name__} needs batch column(s) {missing} "
              "that this batch lacks — the source data has no such "
              "column (e.g. no qid:/field tokens), or the padding layer "
              "dropped it")

    def loss(self, params: Dict[str, Any],
             batch: Dict[str, Any]) -> jnp.ndarray:
        self._check_columns(batch)
        lsum, wsum = self._block_objective(
            params, batch, num_rows=batch["label"].shape[0])
        loss = _weighted_mean(lsum, wsum)
        if self.l2:
            loss = loss + self.l2 * self._l2_term(params)
        return loss

    @partial(jax.jit, static_argnums=0)
    def train_step(self, params, batch):
        loss, grads = jax.value_and_grad(self.loss)(params, batch)
        new_params = jax.tree.map(
            lambda p, g: p - self.learning_rate * g, params, grads)
        return new_params, loss

    def global_loss_fn(self, mesh: Mesh, axis: str = "data"):
        keys = self._BATCH_KEYS + ("label", "weight")

        def _block_loss(params, blk):
            row_bucket = blk["label"].shape[1]
            flat = {k: v[0] for k, v in blk.items()}
            lsum, wsum = self._block_objective(params, flat,
                                               num_rows=row_bucket)
            lsum = jax.lax.psum(lsum, axis)
            wsum = jax.lax.psum(wsum, axis)
            return _weighted_mean(lsum, wsum)

        from jax import shard_map
        # P() is a tree PREFIX covering the whole params dict; batch
        # columns shard on the data axis
        smapped = shard_map(
            _block_loss, mesh=mesh,
            in_specs=(P(), {k: P(axis) for k in keys}),
            out_specs=P())

        def loss(params, batch):
            self._check_columns(batch)
            base = smapped(params, {k: batch[k] for k in keys})
            if self.l2:
                base = base + self.l2 * self._l2_term(params)
            return base
        return loss

    def make_sharded_train_step(self, mesh: Mesh, axis: str = "data"):
        loss_fn = self.global_loss_fn(mesh, axis)
        replicated = NamedSharding(mesh, P())

        @partial(jax.jit, out_shardings=(replicated, replicated))
        def step(params, batch):
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            new_params = jax.tree.map(
                lambda p, g: p - self.learning_rate * g, params, grads)
            return new_params, loss
        return step
