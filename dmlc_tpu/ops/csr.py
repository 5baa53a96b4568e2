"""JAX/XLA ops over CSR batches — the TPU-native compute seam.

The reference has no device compute; on TPU the point of parse-to-HBM is
that downstream learners (XGBoost-style linear/boosted models) consume CSR
batches with XLA-compiled kernels. XLA wants static shapes, so batches are
padded to shape buckets (see dmlc_tpu.parallel.pad_to_bucket) and all ops
here are shape-polymorphic only in the Python sense — under jit each
bucket compiles once.

Representations:
- flat CSR: (offset[n+1], index[nnz], value[nnz]) — SpMV via segment-sum
  (row ids from an O(nnz) prefix count over the offsets; fully jittable,
  no dynamic shapes, no serial loop).
- padded ELL: (index[n, k], value[n, k]) with zero-padded tails — the
  MXU-friendly layout for dense-ish downstream math.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["spmv", "segment_spmv", "csr_to_dense", "csr_to_padded_rows",
           "sdot_rows", "csr_row_ids", "sharded_spmv", "segment_sum"]

# The ONE spelling of segment-sum used across the package (models/fm.py
# and every op here): jax.ops.segment_sum is the supported public API in
# the pinned JAX; if it ever moves, this is the single line to update.
segment_sum = jax.ops.segment_sum


def csr_row_ids(offset: jnp.ndarray, nnz: int) -> jnp.ndarray:
    """row id of every nonzero: row_ids[k] = i s.t. offset[i] <= k < offset[i+1].

    Padded tail entries (k >= offset[-1]) map to row n (one-past-last) so
    segment ops can drop them via num_segments=n.

    Counted, not searched: row_ids[k] = #{i : offset[i] <= k} - 1, so one
    scatter of the n+1 offsets into nnz bins (repeated offsets of empty
    rows share a bin; offsets >= nnz are dropped, they are never <= k) and
    one prefix sum. O(n + nnz) with no serial loop, where searchsorted
    lowers to a binary-search while loop over all nnz positions.
    """
    hits = jnp.zeros((nnz,), jnp.int32).at[offset].add(1, mode="drop")
    return jnp.cumsum(hits) - 1


@partial(jax.jit, static_argnames=("num_rows",))
def segment_spmv(offset: jnp.ndarray, index: jnp.ndarray,
                 value: jnp.ndarray, weights: jnp.ndarray,
                 num_rows: int) -> jnp.ndarray:
    """y[i] = Σ_{k in row i} value[k] * weights[index[k]] (CSR · dense).

    Padded nonzeros must carry value 0 (pad_to_bucket guarantees it), so
    they contribute nothing regardless of their index.
    """
    row_ids = csr_row_ids(offset, index.shape[0])
    contrib = value * jnp.take(weights, index.astype(jnp.int32), axis=0)
    return segment_sum(contrib, row_ids.astype(jnp.int32),
                       num_segments=num_rows)


def spmv(offset, index, value, weights) -> jnp.ndarray:
    """Convenience wrapper: num_rows from offset shape."""
    return segment_spmv(jnp.asarray(offset), jnp.asarray(index),
                        jnp.asarray(value), jnp.asarray(weights),
                        num_rows=int(offset.shape[0]) - 1)


@partial(jax.jit, static_argnames=("num_rows", "num_cols"))
def csr_to_dense(offset: jnp.ndarray, index: jnp.ndarray,
                 value: jnp.ndarray, num_rows: int,
                 num_cols: int) -> jnp.ndarray:
    """Scatter CSR into a dense [num_rows, num_cols] float32 matrix."""
    row_ids = csr_row_ids(offset, index.shape[0]).astype(jnp.int32)
    dense = jnp.zeros((num_rows + 1, num_cols), jnp.float32)
    dense = dense.at[row_ids, index.astype(jnp.int32)].add(value)
    return dense[:num_rows]


def csr_to_padded_rows(offset: np.ndarray, index: np.ndarray,
                       value: Optional[np.ndarray],
                       max_nnz_per_row: Optional[int] = None
                       ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Host-side CSR → padded ELL (index[n,k], value[n,k], mask[n,k]).

    Pad index with 0 and value with 0.0 so downstream gather+MXU matmuls
    are mask-free for linear math.
    """
    offset = np.asarray(offset, np.int64)
    n = len(offset) - 1
    lens = np.diff(offset)
    k = int(max_nnz_per_row if max_nnz_per_row is not None
            else (lens.max() if n else 0))
    out_idx = np.zeros((n, k), np.int32)
    out_val = np.zeros((n, k), np.float32)
    mask = np.zeros((n, k), bool)
    vals = (np.asarray(value, np.float32) if value is not None
            else np.ones(len(index), np.float32))
    for i in range(n):
        m = min(int(lens[i]), k)
        lo = int(offset[i])
        out_idx[i, :m] = index[lo:lo + m]
        out_val[i, :m] = vals[lo:lo + m]
        mask[i, :m] = True
    return out_idx, out_val, mask


@jax.jit
def sdot_rows(padded_index: jnp.ndarray, padded_value: jnp.ndarray,
              weights: jnp.ndarray) -> jnp.ndarray:
    """Batched Row::SDot over padded ELL rows (reference: Row<I>::SDot)."""
    gathered = jnp.take(weights, padded_index.astype(jnp.int32), axis=0)
    return jnp.sum(gathered * padded_value, axis=-1)


def sharded_spmv(batch, weights, mesh, axis: str = "data"):
    """SpMV over a global sharded batch (dmlc_tpu.parallel layout):
    batch arrays are [num_devices, ...] sharded on ``axis``; each device
    computes its own CSR block with static shapes under shard_map;
    weights are replicated. Returns y [num_devices, row_bucket] sharded
    the same way — the canonical consumption pattern for downstream
    learners (per-device partial results, psum-able gradients).
    """
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    row_bucket = batch["offset"].shape[1] - 1

    def block_fn(offset, index, value, w):
        # leading device dim is 1 inside the shard
        return segment_spmv(offset[0], index[0], value[0], w,
                            num_rows=row_bucket)[None]

    fn = shard_map(
        block_fn, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P()),
        out_specs=P(axis))
    return jax.jit(fn)(batch["offset"], batch["index"], batch["value"],
                       jnp.asarray(weights))
