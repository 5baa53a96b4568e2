"""UCI HIGGS rows in CSV text, at the published shape.

A row is the label and 28 features, every value a float32 written as
``%.18e`` (as the released HIGGS.csv writes them: ``8.692932128906250000e-01``).
Each column draws its values from a pool of float32 values that follows
the column's assumed law (the configuration's ``assumed``); a row picks
one value per column from the pools, so rows are distinct while the
text has the published widths and digits.
"""

from __future__ import annotations

from typing import Dict, Iterator, Tuple

import numpy as np

from benchmark.gen import compact, rng_for

VERSION = 1
CHUNK = 16384
WIDTH = 25  # "-d.dddddddddddddddddde-dd"


def _pool(rng: np.random.Generator, law: dict, n: int) -> np.ndarray:
    kind = law["law"]
    if kind == "lognormal":
        v = np.exp(np.log(law["median"]) + law["sigma"] * rng.standard_normal(n))
    elif kind == "normal":
        v = np.clip(rng.standard_normal(n), -law["clip"], law["clip"])
    elif kind == "uniform":
        v = rng.uniform(-law["half_width"], law["half_width"], n)
    elif kind == "discrete":
        v = rng.choice(np.asarray(law["values"]), n, p=law["p"])
    else:
        raise ValueError(f"unknown law {kind!r}")
    return v.astype(np.float32)


def _cells(values: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    chars = np.full((len(values), WIDTH), ord(" "), np.uint8)
    keep = np.zeros((len(values), WIDTH), bool)
    for i, v in enumerate(values):
        s = ("%.18e" % float(v)).encode()
        chars[i, :len(s)] = np.frombuffer(s, np.uint8)
        keep[i, :len(s)] = True
    return chars, keep


def prepare(cfg: dict):
    """What every part shares: per column (label first), the value pool
    and its text cells."""
    a = cfg["assumed"]
    size = a["pool_size"]["value"]
    rng = np.random.default_rng([cfg["corpus"]["seed"], 1 << 20])
    laws = [{"law": "discrete", "values": [0.0, 1.0],
             "p": [1 - a["signal_share"]["value"], a["signal_share"]["value"]]}]
    laws += [a["feature_laws"]["value"][c] for c in cfg["shape"]["columns"][1:]]
    out = []
    for law in laws:
        vals = np.unique(_pool(rng, law, size))
        out.append((vals, *_cells(vals)))
    return out


def _draw(cfg: dict, cols, part: int):
    """Part ``part``'s rows as drawn, a chunk at a time: each row's pick
    from each column's pool, label first."""
    rows = cfg["corpus"]["rows_per_part"]
    rng = rng_for(cfg, part)
    for lo in range(0, rows, CHUNK):
        n = min(CHUNK, rows - lo)
        yield np.stack([rng.integers(0, len(vals), n) for vals, _, _ in cols], 1)


def make_part(cfg: dict, cols, part: int) -> Tuple[bytes, np.ndarray]:
    ncol = len(cols)
    texts, lengths = [], []
    for picks in _draw(cfg, cols, part):
        n = len(picks)
        chars = np.empty((n, ncol, WIDTH + 1), np.uint8)
        keep = np.ones((n, ncol, WIDTH + 1), bool)
        for c, (vals, cc, ck) in enumerate(cols):
            chars[:, c, :WIDTH] = cc[picks[:, c]]
            keep[:, c, :WIDTH] = ck[picks[:, c]]
        chars[:, :, WIDTH] = ord(",")
        chars[:, -1, WIDTH] = ord("\n")
        t, ln = compact(chars.reshape(n, -1), keep.reshape(n, -1))
        texts.append(t)
        lengths.append(ln)
    return b"".join(texts), np.concatenate(lengths)


def rows(cfg: dict, cols, part: int) -> Iterator[Dict[str, np.ndarray]]:
    """Part ``part``'s rows as the reference parses its text (the label
    column first, then the features at indices 0..27), a chunk at a
    time, without the text."""
    for picks in _draw(cfg, cols, part):
        vals = np.stack([v[picks[:, c]] for c, (v, _, _) in enumerate(cols)], 1)
        n, k = vals.shape
        yield {"label": vals[:, 0], "length": np.full(n, k - 1),
               "index": np.tile(np.arange(k - 1), n), "value": vals[:, 1:].reshape(-1)}
