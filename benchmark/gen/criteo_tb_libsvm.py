"""Criteo Terabyte click-log rows in LIBSVM text, at the published shape.

A row is ``label i:v ... i:v``: the click label, then 39 pairs in field
order. Count fields 0..12 sit at indices 1..13 and carry the integer
count (0 where the count is missing); categorical field c takes an index
in its own range of the 10^6 space, drawn by a Zipf law and scattered by
a fixed permutation, with the value 1. The distributions are the
configuration's ``assumed`` entries.
"""

from __future__ import annotations

from typing import Dict, Iterator, Tuple

import numpy as np

from benchmark.gen import compact, digits, rng_for

VERSION = 1
CHUNK = 32768          # rows built at once (bounds the scratch arrays)
IDX_W, VAL_W = 7, 5    # widest index (1000000) and count (65535)


def _params(cfg: dict):
    a = {k: v["value"] for k, v in cfg["assumed"].items()}
    shape = cfg["shape"]
    ncount, ncat = shape["count_fields"], shape["categorical_fields"]
    span = (shape["num_features"] - ncount) // ncat
    return a, ncount, ncat, span


def prepare(cfg: dict) -> dict:
    """What every part shares: the digit tables, the Zipf law and the
    map from categorical ranks to indices."""
    a, ncount, ncat, span = _params(cfg)
    cdf = np.cumsum(np.arange(1, span + 1, dtype=np.float64)
                    ** -a["categorical_zipf_exponent"])
    rng = np.random.default_rng([cfg["corpus"]["seed"], 1 << 20])
    perm = rng.permuted(np.tile(np.arange(span, dtype=np.int64), (ncat, 1)), axis=1)
    return {"idx": digits(np.arange(cfg["shape"]["num_features"] + 1), IDX_W),
            "val": digits(np.arange(a["count_cap"] + 1), VAL_W),
            "zipf_cdf": cdf / cdf[-1],
            "cat_index": perm + ncount + 1 + span * np.arange(ncat)[:, None]}


def _draw(cfg: dict, shared: dict, part: int):
    """Part ``part``'s rows as drawn, a chunk at a time: the labels, and
    each row's 39 indices and values in field order."""
    a, ncount, ncat, span = _params(cfg)
    rows = cfg["corpus"]["rows_per_part"]
    rng = rng_for(cfg, part)
    missing = np.asarray(a["count_missing_share"])
    mu = np.log(np.asarray(a["count_median"], np.float64))
    for lo in range(0, rows, CHUNK):
        n = min(CHUNK, rows - lo)
        label = (rng.random(n) < a["click_rate"]).astype(np.uint8)
        counts = np.floor(np.exp(mu + a["count_log_sigma"]
                                 * rng.standard_normal((n, ncount))))
        counts = np.minimum(counts, a["count_cap"]).astype(np.int64)
        counts[rng.random((n, ncount)) < missing] = 0
        ranks = np.minimum(np.searchsorted(shared["zipf_cdf"],
                                           rng.random((n, ncat))), span - 1)
        cat = shared["cat_index"][np.arange(ncat), ranks]
        idx = np.concatenate(
            [np.broadcast_to(np.arange(1, ncount + 1), (n, ncount)), cat], 1)
        val = np.concatenate([counts, np.ones((n, ncat), np.int64)], 1)
        yield label, idx, val


def make_part(cfg: dict, shared: dict, part: int) -> Tuple[bytes, np.ndarray]:
    (ic, ik), (vc, vk) = shared["idx"], shared["val"]
    texts, lengths = [], []
    for label, idx, val in _draw(cfg, shared, part):
        n, k = idx.shape
        width = 1 + IDX_W + 1 + VAL_W
        tok = np.empty((n, k, width), np.uint8)
        keep = np.ones(tok.shape, bool)
        tok[:, :, 0] = ord(" ")
        tok[:, :, 1:1 + IDX_W], keep[:, :, 1:1 + IDX_W] = ic[idx], ik[idx]
        tok[:, :, 1 + IDX_W] = ord(":")
        tok[:, :, 2 + IDX_W:], keep[:, :, 2 + IDX_W:] = vc[val], vk[val]
        nl = np.full((n, 1), ord("\n"), np.uint8)
        chars = np.concatenate([(label + 48)[:, None], tok.reshape(n, -1), nl], 1)
        keeps = np.concatenate([np.ones((n, 1), bool), keep.reshape(n, -1),
                                np.ones((n, 1), bool)], 1)
        t, ln = compact(chars, keeps)
        texts.append(t)
        lengths.append(ln)
    return b"".join(texts), np.concatenate(lengths)


def rows(cfg: dict, shared: dict, part: int) -> Iterator[Dict[str, np.ndarray]]:
    """Part ``part``'s rows as the reference parses its text, a chunk at
    a time, without the text."""
    for label, idx, val in _draw(cfg, shared, part):
        n, k = idx.shape
        yield {"label": label.astype(np.float32), "length": np.full(n, k),
               "index": idx.reshape(-1), "value": val.reshape(-1).astype(np.float32)}
