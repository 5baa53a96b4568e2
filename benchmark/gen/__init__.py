"""Corpus generation and the corpus cache.

Each configuration has a generator module of its own name here
(``benchmark/gen/<config>.py``) exposing ``VERSION``,
``prepare(cfg)`` (what every part shares) and
``make_part(cfg, shared, part) -> (text bytes, row lengths)``. A corpus is a
fixed set of part files per configuration and generator version, made
from a constant seed, so a checkout writes it once. ``--seed`` orders
the parts (``Stream``): every seed reads the same rows, in another order.
Per-seed corpora would write some 2 GiB per seed and side, about 100 GiB
in one check of four cells, and the check then changes machines.

The generators copy nothing from ``dmlc_tpu`` and import only NumPy.
"""

from __future__ import annotations

import importlib
import json
import os
import time
from concurrent.futures import ThreadPoolExecutor
from typing import List, Optional, Tuple

import numpy as np

CACHE = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     ".cache")


def generator(cfg: dict):
    return importlib.import_module(f"benchmark.gen.{cfg['name']}")


def ensure_corpus(cfg: dict, cache: str = CACHE) -> Tuple["Corpus", dict]:
    """The configuration's corpus, generated on first use. Returns it and
    what this call did (``generated`` is False when the cache served it)."""
    d = os.path.join(cache, "corpus", f"{cfg['name']}-v{generator(cfg).VERSION}")
    done = os.path.join(d, "done.json")
    t0 = time.perf_counter()
    generated = False
    if not os.path.exists(done):
        gen = generator(cfg)
        os.makedirs(d, exist_ok=True)
        shared = gen.prepare(cfg)

        def one(k: int) -> dict:
            text, lengths = gen.make_part(cfg, shared, k)
            path = os.path.join(d, f"part-{k:02d}.txt")
            with open(path + ".tmp", "wb") as f:
                f.write(text)
            os.replace(path + ".tmp", path)
            np.save(os.path.join(d, f"part-{k:02d}.len.npy"), lengths)
            return {"rows": int(len(lengths)), "bytes": len(text)}

        # NumPy drops the GIL in the gathers and masks, so parts made on
        # threads overlap
        with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
            parts = list(pool.map(one, range(cfg["corpus"]["parts"])))
        with open(done + ".tmp", "w") as f:
            json.dump({"parts": parts}, f)
        os.replace(done + ".tmp", done)
        generated = True
    corpus = Corpus.load(d)
    return corpus, {"generated": generated,
                    "corpus_s": time.perf_counter() - t0,
                    "bytes": corpus.total_bytes, "rows": corpus.total_rows}


class Corpus:
    """The part files in their canonical order, with each row's length."""

    def __init__(self, files: List[str], lengths: List[np.ndarray]):
        self.files = files
        self.lengths = lengths
        self.total_bytes = int(sum(int(x.sum()) for x in lengths))
        self.total_rows = int(sum(len(x) for x in lengths))

    @classmethod
    def load(cls, d: str) -> "Corpus":
        with open(os.path.join(d, "done.json")) as f:
            n = len(json.load(f)["parts"])
        files = [os.path.join(d, f"part-{k:02d}.txt") for k in range(n)]
        lengths = [np.load(os.path.join(d, f"part-{k:02d}.len.npy"))
                   for k in range(n)]
        return cls(files, lengths)

    def stream(self, seed: Optional[int]) -> "Stream":
        """The parts in the order ``seed`` draws; None keeps the
        canonical order."""
        order = list(range(len(self.files)))
        if seed is not None:
            rng = np.random.default_rng(seed % (1 << 64))
            order = [int(k) for k in rng.permutation(len(self.files))]
        return Stream([self.files[k] for k in order],
                      [self.lengths[k] for k in order], order)


class Stream:
    """Rows of the corpus in one order: the files as one byte space,
    as a ``;``-joined URI reads them."""

    def __init__(self, files: List[str], lengths: List[np.ndarray], order: List[int]):
        self.files = files
        # order[k]: the corpus part (canonical place) of the k-th file
        self.order = order
        self.uri = ";".join(files)
        lens = np.concatenate(lengths).astype(np.int64)
        # start[r] = global byte offset of row r; start[-1] = total
        self.start = np.concatenate([[0], np.cumsum(lens)])
        self.rows = len(lens)
        self.total_bytes = int(self.start[-1])
        sizes = [int(x.astype(np.int64).sum()) for x in lengths]
        self.file_start = np.concatenate([[0], np.cumsum(sizes)])
        self.file_first_row = np.concatenate(
            [[0], np.cumsum([len(x) for x in lengths])])

    def lines(self, a: int, b: int) -> List[bytes]:
        """The text of rows [a, b), one line each, without its newline."""
        out: List[bytes] = []
        r = a
        while r < b:
            f = int(np.searchsorted(self.file_first_row, r, side="right")) - 1
            end = min(b, int(self.file_first_row[f + 1]))
            lo = int(self.start[r] - self.file_start[f])
            hi = int(self.start[end] - self.file_start[f])
            with open(self.files[f], "rb") as fh:
                fh.seek(lo)
                buf = fh.read(hi - lo)
            out.extend(buf.split(b"\n")[:end - r])
            r = end
        return out

    def lines_at(self, rows: np.ndarray) -> List[bytes]:
        """The text of each of ``rows``, in their order, one line each;
        each run of consecutive rows is read at once."""
        rows = np.asarray(rows, np.int64)
        cut = np.flatnonzero(np.diff(rows) != 1) + 1
        out: List[bytes] = []
        for run in np.split(rows, cut) if len(rows) else []:
            out.extend(self.lines(int(run[0]), int(run[-1]) + 1))
        return out

    def part_first_rows(self, nparts: int) -> np.ndarray:
        """First row of each of ``nparts`` byte-range parts, and the row
        count at the end: dmlc's split rule. Part k's nominal start is
        ``min(ceil(total / nparts) * k, total)``; a start at a file
        boundary stays, any other moves to the first row that begins
        after it."""
        nstep = -(-self.total_bytes // nparts)
        out = []
        for k in range(nparts + 1):
            x = min(nstep * k, self.total_bytes)
            if x in self.file_start:
                out.append(int(np.searchsorted(self.start, x)))
            else:
                out.append(int(np.searchsorted(self.start, x, side="right")))
        return np.asarray(out)


# -- text assembly shared by the generators


def digits(x: np.ndarray, width: int) -> Tuple[np.ndarray, np.ndarray]:
    """Decimal digits of the non-negative integers ``x``, right-aligned
    in ``width`` ASCII columns, and the mask of digits to keep (no
    leading zeros; 0 keeps one)."""
    x = np.asarray(x, np.int64)[..., None]
    pow10 = 10 ** np.arange(width - 1, -1, -1, dtype=np.int64)
    chars = ((x // pow10) % 10 + 48).astype(np.uint8)
    ndig = np.maximum((np.maximum(x, 1) >= pow10).sum(-1, keepdims=True), 1)
    keep = np.arange(width) >= width - ndig
    return chars, keep


def compact(chars: np.ndarray, keep: np.ndarray) -> Tuple[bytes, np.ndarray]:
    """Rows of fixed-width ASCII cells → the kept bytes, row-major, and
    each row's byte count."""
    return chars[keep].tobytes(), keep.sum(axis=1).astype(np.uint16)


def rng_for(cfg: dict, part: int) -> np.random.Generator:
    """Part ``part``'s generator: from the configuration's constant
    corpus seed, independent of every other part."""
    return np.random.default_rng([cfg["corpus"]["seed"], part])
