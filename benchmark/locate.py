"""Where a delivered row came from, found by its content.

Rows that arrive out of source order (a shuffle before the parse) cannot
be matched with the corpus by their place in the delivery. The locator
keys every row of the corpus by a 64-bit hash of its parsed content
(label, row length, and the multiset of its pairs' indices and values),
computed from the generator's own arrays re-derived from the corpus
seed (``gen.<config>.rows``), not from the text. The keys are sorted
once and kept next to the corpus (``locator.npz``, per configuration
and generator version), built on first use.

The first 32-bit lane of the same key is taken on the device of every
row the window delivers (``device_keys``), so that the deliveries of
each content in an epoch can be counted against the rows of the part
that hold it (``excess``).

It only proposes candidates. A delivered row is matched with a source
line only where the reference parse of that line equals it exactly
(``drive``), so a fault here can make a sound row count as mismatched
or repeated, never a wrong row pass.
"""

from __future__ import annotations

import os
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Tuple

import numpy as np

from benchmark.gen import Corpus, Stream, generator

# one seed per 32-bit lane of a key
LANES = (0x9E3779B9, 0x7F4A7C15)


def _fmix(x):
    """murmur3's 32-bit finaliser, on uint32 arrays (wrapping)."""
    x = x ^ (x >> 16)
    x = x * np.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * np.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def _lanes(xp, bits, label, offset, index, value, nnz, seeds=LANES):
    """The lanes of each row's key for ``seeds``, with array module
    ``xp`` (NumPy or jax.numpy) and ``bits`` (a float32 array's bits as
    uint32): rows are ``offset``'s spans of the pairs, pairs from
    ``nnz`` on are padding. A row's pairs are summed, so their order
    does not count; the exact comparison holds them to it."""
    u32 = xp.uint32
    idx = index.astype(u32)
    # 0.0 and -0.0, which the exact comparison holds equal, alike
    val = xp.where(value == 0, u32(0), bits(value))
    lab = xp.where(label == 0, u32(0), bits(label))
    live = xp.arange(index.shape[-1]) < nnz
    length = (offset[1:] - offset[:-1]).astype(u32)
    out = []
    for seed in seeds:
        pair = xp.where(live, _fmix(_fmix(idx ^ u32(seed)) ^ val), u32(0))
        total = xp.concatenate([xp.zeros(1, u32), xp.cumsum(pair, dtype=u32)])
        per_row = total[offset[1:]] - total[offset[:-1]]
        out.append(_fmix(per_row ^ _fmix(lab ^ u32(seed))
                         ^ _fmix(length * u32(0x9E3779B1) + u32(seed))))
    return out


def first_lane(keys: np.ndarray) -> np.ndarray:
    """The first lane of uint64 keys, as ``device_keys`` takes it."""
    return (np.asarray(keys, np.uint64) >> np.uint64(32)).astype(np.uint32)


def row_keys(rows: Dict[str, np.ndarray]) -> np.ndarray:
    """One uint64 key per row of ``rows`` (the reference's layout:
    ``label``, ``length``, then each pair's ``index`` and ``value``).
    The weight is left out: a row whose weight is wrong is still found,
    and the exact comparison then counts it."""
    length = np.asarray(rows["length"], np.int64)
    offset = np.concatenate([[0], np.cumsum(length)])
    index = np.asarray(rows["index"], np.int64)
    hi, lo = _lanes(np, lambda v: v.view(np.uint32), np.asarray(rows["label"], np.float32),
                    offset, index, np.asarray(rows["value"], np.float32), len(index))
    return (hi.astype(np.uint64) << np.uint64(32)) | lo.astype(np.uint64)


def device_keys(b):
    """The first lane of each row's key of padded batch ``b`` where it
    lies (a uint32 a row, or a row of them per chip on a mesh), the same
    arithmetic as ``row_keys``; rows past ``num_rows`` are padding."""
    import jax
    import jax.numpy as jnp

    def one(label, offset, index, value, nnz):
        return _lanes(jnp, lambda v: jax.lax.bitcast_convert_type(v, jnp.uint32),
                      label, offset, index, value, nnz, LANES[:1])[0]

    f = one if np.ndim(b["num_rows"]) == 0 else jax.vmap(one)
    return f(b["label"], b["offset"], b["index"], b["value"], b["num_nnz"])


class Locator:
    """The corpus's row keys, sorted, each with its row in the canonical
    order of the parts."""

    def __init__(self, keys: np.ndarray, rows: np.ndarray, part_first: np.ndarray):
        self.keys, self.rows, self.part_first = keys, rows, part_first

    def _canon(self, stream: Stream, rows: np.ndarray) -> np.ndarray:
        """The canonical row of each of ``stream``'s ``rows``."""
        file = np.searchsorted(stream.file_first_row, rows, side="right") - 1
        part = np.asarray(stream.order)[file]
        return self.part_first[part] + rows - stream.file_first_row[file]

    def candidates(self, stream: Stream, keys: np.ndarray, lo: int,
                   hi: int) -> List[np.ndarray]:
        """For each of ``keys``, the rows of ``stream`` in [lo, hi) that
        have it, in stream order."""
        a = np.searchsorted(self.keys, keys, side="left")
        n = np.searchsorted(self.keys, keys, side="right") - a
        first = np.cumsum(n) - n
        canon = self.rows[np.repeat(a - first, n) + np.arange(int(n.sum()))].astype(np.int64)
        part = np.searchsorted(self.part_first, canon, side="right") - 1
        at = np.empty(len(stream.order), np.int64)
        at[np.asarray(stream.order)] = np.arange(len(stream.order))
        rows = stream.file_first_row[at[part]] + canon - self.part_first[part]
        return [np.sort(r[(r >= lo) & (r < hi)]) for r in np.split(rows, first[1:])]

    def part_keys(self, stream: Stream, lo: int, hi: int) -> np.ndarray:
        """The keys of ``stream``'s rows in [lo, hi), sorted."""
        by_canon = np.empty(len(self.keys), np.uint64)
        by_canon[self.rows] = self.keys
        return np.sort(by_canon[self._canon(stream, np.arange(lo, hi))])

    def excess(self, stream: Stream, first: np.ndarray, lanes: np.ndarray,
               num_rows: np.ndarray, epochs: np.ndarray) -> int:
        """Deliveries of a content beyond the rows of their chip's part
        that hold it, summed over epochs and chips: ``lanes[i, d]`` are
        the first lanes of the keys of the i-th delivered batch's rows on
        chip d, of which the first ``num_rows[i, d]`` are rows. A content
        that no row of the part holds counts at each delivery. Rows of a
        part whose first lanes coincide count together: that can hide a
        repeat only where the same fault drops such a row, and never
        fails a sound run."""
        out = 0
        for d in range(len(first) - 1):
            have = first_lane(self.part_keys(stream, int(first[d]), int(first[d + 1])))
            for e in np.unique(epochs):
                got = np.concatenate([lanes[i, d, :num_rows[i, d]]
                                      for i in np.flatnonzero(epochs == e)])
                k, n = np.unique(got, return_counts=True)
                held = (np.searchsorted(have, k, side="right")
                        - np.searchsorted(have, k, side="left"))
                out += int(np.maximum(n - held, 0).sum())
        return out


def ensure_locator(cfg: dict, corpus: Corpus) -> Tuple[Locator, dict]:
    """The corpus's locator, built on first use next to its part files.
    Returns it and what this call did (``built``, ``locator_s``)."""
    path = os.path.join(os.path.dirname(corpus.files[0]), "locator.npz")
    t0 = time.perf_counter()
    built = not os.path.exists(path)
    part_first = np.concatenate([[0], np.cumsum([len(x) for x in corpus.lengths])])
    if built:
        gen = generator(cfg)
        shared = gen.prepare(cfg)

        def one(k: int) -> np.ndarray:
            keys = np.concatenate([row_keys(r) for r in gen.rows(cfg, shared, k)])
            if len(keys) != len(corpus.lengths[k]):
                raise RuntimeError(f"locator: the generator drew {len(keys)} rows "
                                   f"for part {k}, the corpus holds {len(corpus.lengths[k])}")
            return keys

        with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
            keys = np.concatenate(list(pool.map(one, range(len(corpus.files)))))
        order = np.argsort(keys, kind="stable")
        rows = order.astype(np.min_scalar_type(max(len(keys) - 1, 0)))
        with open(path + ".tmp", "wb") as f:
            np.savez(f, keys=keys[order], rows=rows)
        os.replace(path + ".tmp", path)
    with np.load(path) as z:
        loc = Locator(z["keys"], z["rows"], part_first)
    return loc, {"built": built, "locator_s": time.perf_counter() - t0}
