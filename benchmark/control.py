"""The readings that the limits of ``correct`` are set from, taken by
the benchmark's own run (``drive.run``) with the timed path broken
underneath. The benchmark's own runs never run this.

    python3 benchmark/control.py --workload <cell> --seeds 11,12,13 \
        [--plants prog,control] [--seconds 2] [--root DIR --cache DIR]

``--root`` and ``--cache`` take the cell from another checkout's
``BENCHMARK.json`` and files, such as one that
``tests/benchmark/bench_helpers.py`` writes with cells of its own.

Plants, each put in at the program's boundary:

- ``prog``: nothing; the sound program (the lower readings).
- ``control``: the reference, computed in bfloat16, in the program's
  place: every delivered batch's values rounded to bfloat16 where the
  pipeline yields it, and the SGD step replaced by the reference's step
  in bfloat16 (the upper readings).
- ``half``: half of each batch left out, the mean taken over the rest
  (the second half of the rows weighted 0 where the batch is yielded).
- ``altered``: one value of each batch altered where it is yielded.
- ``unchanged``: a step that returns its state unchanged.
- ``exchange``: on a mesh, the sums between chips not exchanged (chip
  0's own stand for all of them).
- ``unshuffled``: the shuffle step dropped (``Pipeline.shuffle`` returns
  the pipeline as it was), while the traffic still says its rows arrive
  out of source order.
- ``repeated``: each batch's second row replaced by its first where the
  batch is yielded.
- ``twice``: the source reads its first file again in place of its
  last (``Pipeline.from_uri`` given the URI so changed): every epoch
  delivers one part of the corpus twice and another never, through
  whichever engine parses it and whatever order a shuffle puts the
  parts in.

Prints one JSON line per seed and plant (``correct``, each number
compared, and the run's epochs, engine, assembly path and locator
seconds), then, per plant, the largest and smallest of each number.
"""

import argparse
import contextlib
import json
import os
import sys
import time

# run as a script, Python puts benchmark/ first on the path; the
# checkout root holds both the benchmark and the program under test
sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ROW_KEYS = ("label", "weight", "length", "index", "value")


class _Over:
    """A module with some attributes replaced, for the program's module
    alone (JAX's own code keeps the real ones)."""

    def __init__(self, mod, **over):
        self._mod = mod
        self.__dict__.update(over)

    def __getattr__(self, name):
        return getattr(self._mod, name)


def _ref_step(lr, params, batch):
    """The reference's SGD step in bfloat16 on the rows of ``batch``, all
    shards together, as the program's global step takes them."""
    import jax
    import numpy as np
    from benchmark import ref
    rows = ref.concat([{k: r[k] for k in ROW_KEYS} for r in
                       map(ref.device_rows, ref.shards(jax.device_get(batch)))])
    w, b, loss = ref.sgd_step(np.asarray(params["w"], np.float64),
                              float(np.asarray(params["b"])), rows, lr, lower=True)
    return {"w": w.astype(np.float32), "b": np.float32(b)}, np.float32(loss)


def _repeat_first(b):
    """``b`` with each shard's second row replaced by its first, on the
    devices it came from."""
    import jax
    import numpy as np
    h = {k: np.array(v) for k, v in jax.device_get(b).items()}
    mesh = np.ndim(h["num_rows"]) > 0
    for at in [(d,) for d in range(len(h["num_rows"]))] if mesh else [()]:
        n, off = int(h["num_rows"][at]), h["offset"][at].astype(np.int64)
        if n < 2:
            continue
        take = np.concatenate([np.arange(off[0], off[1]), np.arange(off[0], off[1]),
                               np.arange(off[2], off[n])])
        for k in ("index", "value"):
            a = h[k][at]
            a[off[0]:off[0] + len(take)], a[off[0] + len(take):off[n]] = a[take], 0
        length = np.diff(off[:n + 1])
        length[1] = length[0]
        o = h["offset"][at]
        o[1:n + 1] = off[0] + np.cumsum(length)
        o[n + 1:] = o[n]
        h["num_nnz"][at] = o[n] - off[0]
        for k in ("label", "weight"):
            h[k][at][1] = h[k][at][0]
    return {k: jax.device_put(h[k], v.sharding) if hasattr(v, "sharding") else h[k]
            for k, v in b.items()}


def _first_file_twice(uri: str) -> str:
    """``uri``, a ``;``-joined list of files, with its first file in the
    place of its last."""
    files = uri.split(";")
    if len(files) < 2:
        raise ValueError(f"the twice plant needs a source of two files or more: {uri!r}")
    return ";".join(files[:-1] + files[:1])


@contextlib.contextmanager
def planted(plant):
    """The program with ``plant`` put in (see the module's doc); None or
    ``prog`` leaves it sound."""
    import jax
    import jax.numpy as jnp
    from dmlc_tpu.models import common
    from dmlc_tpu.models.common import SparseModelBase
    from dmlc_tpu.pipeline import CompiledPipeline, Pipeline

    tamper = {
        "control": lambda b: dict(b, value=b["value"].astype(jnp.bfloat16).astype(jnp.float32)),
        "half": lambda b: dict(b, weight=b["weight"].at[..., b["weight"].shape[-1] // 2:].set(0)),
        "altered": lambda b: dict(b, value=b["value"].at[..., 0].add(1)),
        "repeated": _repeat_first,
    }.get(plant)
    sharded = SparseModelBase.make_sharded_train_step
    step = None
    if plant == "control":
        def step(self, params, batch):
            return _ref_step(self.learning_rate, params, batch)

        def step_on_mesh(self, mesh, axis="data"):
            return lambda params, batch: step(self, params, batch)
    elif plant == "unchanged":
        def step(self, params, batch):
            return params, self.loss(params, batch)

        def step_on_mesh(self, mesh, axis="data"):
            f = sharded(self, mesh, axis)
            return lambda params, batch: (params, f(params, batch)[1])
    with contextlib.ExitStack() as stack:
        def put(obj, name, value):
            old = getattr(obj, name)
            setattr(obj, name, value)
            stack.callback(setattr, obj, name, old)

        if tamper is not None:
            produce = CompiledPipeline.__iter__
            put(CompiledPipeline, "__iter__",
                lambda self: (tamper(b) for b in produce(self)))
        if step is not None:
            put(SparseModelBase, "train_step", step)
            put(SparseModelBase, "make_sharded_train_step", step_on_mesh)
        if plant == "unshuffled":
            put(Pipeline, "shuffle", lambda self, *a, **kw: self)
        if plant == "twice":
            source = Pipeline.__dict__["from_uri"]
            Pipeline.from_uri = staticmethod(
                lambda uri, *a, **kw: source.__func__(_first_file_twice(uri), *a, **kw))
            stack.callback(setattr, Pipeline, "from_uri", source)
        if plant == "exchange":
            def chip0(x, axis):
                return jax.lax.psum(jnp.where(jax.lax.axis_index(axis) == 0, x, 0), axis)
            put(common, "jax", _Over(jax, lax=_Over(jax.lax, psum=chip0)))
        yield


def _run(name: str, seed: int, plant, seconds: float, **kw) -> dict:
    from benchmark import drive
    with planted(plant):
        return drive.run(name, seed, seconds, False, time.perf_counter(), **kw)


def _numbers(out: dict) -> dict:
    return {"correct": out["correct"], **{k: c["value"] for k, c in out["checks"].items()}}


def reading(name: str, seed: int, plant=None, seconds: float = 2.0, **kw) -> dict:
    """One run of cell ``name`` through ``drive.run`` with ``plant`` put
    in: ``correct`` and each number compared."""
    return _numbers(_run(name, seed, plant, seconds, **kw))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--plants", default="prog,control")
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--root")
    ap.add_argument("--cache")
    args = ap.parse_args(argv)
    kw = {k: getattr(args, k) for k in ("root", "cache") if getattr(args, k)}
    from benchmark import drive
    plants = args.plants.split(",")
    rows = []
    try:
        for s in args.seeds.split(","):
            for p in plants:
                t = time.perf_counter()
                out = _run(args.workload, int(s), p, args.seconds, **kw)
                info = {k: out["info"][k] for k in ("epochs_done", "batches", "engine",
                                                    "assembly_path", "locator_s")
                        if k in out["info"]}
                rows.append(dict(_numbers(out), seed=int(s), plant=p,
                                 s=time.perf_counter() - t, info=info))
                print(json.dumps(rows[-1]), flush=True)
    except drive.NoDevice as e:
        print(f"control: {e}", file=sys.stderr)
        return 3
    summary = {}
    for p in plants:
        mine = [r for r in rows if r["plant"] == p]
        summary[p] = {"runs": len(mine), "correct": sum(r["correct"] for r in mine)}
        for k in sorted({k for r in mine for k in r} - {"correct", "seed", "plant", "s", "info"}):
            vals = [r[k] for r in mine if k in r]
            summary[p][k] = {"min": min(vals), "max": max(vals)}
    print(json.dumps({"workload": args.workload, "summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
