"""JAX/XLA ops over CSR batches — the TPU-native compute seam.

The reference has no device compute; on TPU the point of parse-to-HBM is
that downstream learners (XGBoost-style linear/boosted models) consume CSR
batches with XLA-compiled kernels. XLA wants static shapes, so batches are
padded to shape buckets (see dmlc_tpu.parallel.pad_to_bucket) and all ops
here are shape-polymorphic only in the Python sense — under jit each
bucket compiles once.

Representations:
- flat CSR: (offset[n+1], index[nnz], value[nnz]) — SpMV row sums by a
  segmented scan over each row's contiguous run of nonzeros, read at the
  row ends (fully jittable, no dynamic shapes, no serial loop).
- padded ELL: (index[n, k], value[n, k]) with zero-padded tails — the
  MXU-friendly layout for dense-ish downstream math.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["spmv", "segment_spmv", "csr_row_sum", "csr_to_dense",
           "csr_to_padded_rows", "sdot_rows", "csr_row_ids", "sharded_spmv",
           "segment_sum"]

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


_LANES = 128


def _shift(a: jnp.ndarray, d: int, axis: int) -> jnp.ndarray:
    """``a`` moved ``d`` places up ``axis``, zeros in front."""
    pad = [(0, 0)] * a.ndim
    pad[axis] = (d, 0)
    return jax.lax.slice_in_dim(jnp.pad(a, pad), 0, a.shape[axis],
                                axis=axis)


def _row_rank(offset: jnp.ndarray, nnz: int) -> jnp.ndarray:
    """Each nonzero's place in its row, k - offset[i] for offset[i] <= k <
    offset[i+1] (the padded tail counts from offset[-1]): each offset
    written at its own position (offsets >= nnz dropped), a running max,
    an iota less it. Int32, from the offsets alone: on a v5e this is work
    the compiler runs while the weight table moves into VMEM for the
    weight gather (start flags carried through the scan left the gather
    reading HBM, 2.85 ms against 1.14 at 4096 x 39)."""
    start = offset.astype(jnp.int32)
    first = jnp.zeros((nnz,), jnp.int32).at[start].set(start, mode="drop")
    return jnp.arange(nnz, dtype=jnp.int32) - jax.lax.cummax(first)


def _segmented_cumsum(rank: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive prefix sum of ``x`` [nnz] within each row, ``rank`` the
    place of each entry in its row (``_row_rank``), so each sum holds only
    its own row's terms.

    On a lane-aligned [nnz/128, 128] view: a log-step scan inside each
    row of 128 (add the entry d places back while it is in the same
    row), the same over the rows' last sums (the carries), and the carry
    added where a CSR row runs on from the lane row before.
    """
    nnz = x.shape[0]
    pad = -nnz % _LANES
    rank = jnp.pad(rank, (0, pad)).reshape(-1, _LANES)
    x = jnp.pad(x, (0, pad)).reshape(-1, _LANES)
    d = 1
    while d < _LANES:
        x = x + jnp.where(rank >= d, _shift(x, d, axis=1), 0)
        d *= 2
    last, carry = rank[:, -1], x[:, -1]
    d = 1
    while d < carry.shape[0]:
        carry = carry + jnp.where(last >= d * _LANES,
                                  _shift(carry, d, axis=0), 0)
        d *= 2
    lane = jnp.arange(_LANES, dtype=rank.dtype)
    x = x + jnp.where(rank > lane, _shift(carry, 1, axis=0)[:, None], 0)
    return x.reshape(-1)[:nnz]


def _row_sum_fwd(offset, contrib, num_rows):
    rank = _row_rank(offset, contrib.shape[0])
    if not contrib.shape[0]:  # nothing to read: every row is empty
        return jnp.zeros((num_rows,), contrib.dtype), (offset, rank)
    sums = _segmented_cumsum(rank, contrib)
    lo, hi = offset[:num_rows], offset[1:num_rows + 1]
    return jnp.where(hi > lo, sums[jnp.maximum(hi - 1, 0)], 0), (offset, rank)


def _row_sum_bwd(num_rows, res, g):
    # each row's cotangent put at its first nonzero and carried over the
    # row by the same scan: a broadcast over the run, not a gather
    offset, rank = res
    lo, hi = offset[:num_rows], offset[1:num_rows + 1]
    first = jnp.zeros(rank.shape, g.dtype).at[lo].add(
        jnp.where(hi > lo, g, 0), mode="drop")
    return None, _segmented_cumsum(rank, first)


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def csr_row_sum(offset: jnp.ndarray, contrib: jnp.ndarray,
                num_rows: int) -> jnp.ndarray:
    """y[i] = Σ contrib[offset[i]:offset[i+1]] for i < num_rows.

    ``offset`` is a CSR batch's sorted offsets (at least num_rows + 1 of
    them, none past len(contrib)), so each row is one contiguous run: the
    sum is a segmented scan read at each row's last nonzero (0 for an
    empty row), and its transpose spreads each row's cotangent over its
    run by the same scan. Index operations touch only the n + 1 offsets;
    each row sums only its own terms, in contrib's dtype.
    """
    return _row_sum_fwd(offset, contrib, num_rows)[0]


csr_row_sum.defvjp(_row_sum_fwd, _row_sum_bwd)


@partial(jax.jit, static_argnames=("num_rows",))
def segment_spmv(offset: jnp.ndarray, index: jnp.ndarray,
                 value: jnp.ndarray, weights: jnp.ndarray,
                 num_rows: int) -> jnp.ndarray:
    """y[i] = Σ_{k in row i} value[k] * weights[index[k]] (CSR · dense).

    Row sums by ``csr_row_sum``'s segmented scan over the sorted offsets.
    Padded nonzeros must carry value 0 (pad_to_bucket guarantees it), so
    they contribute nothing regardless of their index.
    """
    contrib = value * jnp.take(weights, index.astype(jnp.int32), axis=0)
    return csr_row_sum(offset, contrib, num_rows)


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
