"""A checkout in miniature for the benchmark's CPU tests: the real
BENCHMARK.json, traffic and limits files, with each configuration's
corpus and batch cut to a size a test run holds, and two cells of its
own whose rows arrive out of source order (``SHUFFLED``), and two of
the chunk-shuffled traffic that state an engine (``STATED``).

As a script it writes such a checkout, or with ``--full`` one at the
configurations' own sizes, for ``benchmark/control.py --root``:

    python3 tests/benchmark/bench_helpers.py DIR [--full]
"""

import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if __name__ == "__main__":
    sys.path[0] = REPO

from benchmark.drive import RUN_SEED  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
# test-only cells whose traffic shuffles the rows before the parse:
# chunk-level (InputSplitShuffle) and sample-level (GlobalShuffleSplit)
SHUFFLED = {
    "criteo_tb.shuffled_chunks": [["shuffle", {"num_shuffle_parts": 16, "seed": RUN_SEED}]],
    "criteo_tb.shuffled_global": [["shuffle", {"global_seed": RUN_SEED}]],
}
# test-only cells of the chunk-shuffled traffic that state the engine
# that must serve its parse: the one the program picks reads correct
STATED = {"criteo_tb.shuffled_native": "native", "criteo_tb.shuffled_python": "python"}
SMALL = {"criteo_tb_libsvm": dict(parts=3, rows_per_part=700, batch_rows=128),
         "higgs_csv": dict(parts=3, rows_per_part=500, batch_rows=128)}


def config(name: str, full: bool = False) -> dict:
    with open(os.path.join(REPO, "benchmark", "configs", name + ".json")) as f:
        cfg = json.load(f)
    if full:
        return cfg
    s = SMALL[name]
    cfg["corpus"].update(parts=s["parts"], rows_per_part=s["rows_per_part"])
    cfg["batch_rows"] = s["batch_rows"]
    return cfg


def tiny_root(tmp, full: bool = False) -> tuple:
    root = os.path.join(str(tmp), "checkout")
    os.makedirs(os.path.join(root, "benchmark", "configs"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    for d in ("traffic", "limits"):
        shutil.copytree(os.path.join(REPO, "benchmark", d),
                        os.path.join(root, "benchmark", d))
    for name in SMALL:
        with open(os.path.join(root, "benchmark", "configs", name + ".json"), "w") as f:
            json.dump(config(name, full), f)
    _add_shuffled(root)
    return root, os.path.join(str(tmp), "cache")


def _add_shuffled(root: str) -> None:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(root, "benchmark", "limits", "criteo_tb.stream_train.json")) as f:
        limits = json.load(f)
    limits.update(rows_repeated={"limit": 0}, rows_in_source_order={"limit": 2})
    cells = {c: {"before_parse": steps} for c, steps in SHUFFLED.items()}
    cells.update({c: {"before_parse": SHUFFLED["criteo_tb.shuffled_chunks"], "engine": e}
                  for c, e in STATED.items()})
    for cell, more in cells.items():
        mix = cell.split(".")[1]
        bench["workloads"].append({"name": cell, "config": "criteo_tb_libsvm",
                                   "traffic": mix, "chips": 1, "why": "test only"})
        traffic = {"why": "test only", "rows": "out_of_order", "consumer": "sgd", **more}
        held = dict(limits, engine_served={"limit": 0}) if "engine" in more else limits
        for d, name, obj in (("traffic", mix, traffic), ("limits", cell, held)):
            with open(os.path.join(root, "benchmark", d, name + ".json"), "w") as f:
                json.dump(obj, f)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)


if __name__ == "__main__":
    out = tiny_root(sys.argv[1], full="--full" in sys.argv[2:])
    print(json.dumps({"root": out[0], "cache": out[1], "cells": sorted({**SHUFFLED, **STATED})}))
