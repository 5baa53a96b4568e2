"""Chip smoke test: the main path once, on the chip, checked.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the data-parallel path on 4 chips

One chip: a criteo-shaped libsvm corpus (``ensure_data``, 256 MB)
→ native parse and padded batch assembly → HBM through the
Pipeline API → ``SparseLinearModel(2**20).train_step`` on the
device-resident batches. Checks: the epoch used the native padded
assembly; the batches read back from HBM hash equal to a host-side
parse of the file; the native parse is byte-identical to the Python
golden engine on a 16 MB part; every loss is finite and matches a
NumPy float64 replay of the same SGD steps.

``--chips 4``: ``ShardedRowBlockIter`` over a 4-chip ``("data",)``
mesh feeding ``make_sharded_train_step``, and nothing else. Checks:
every batch has a shard on each of the four chips; the four per-chip
streams, joined in mesh order, hash equal to the one-chip stream of
the same file; the sharded steps' losses and parameters match the
same steps computed on one chip.

Each phase prints one JSON line. The last line is
``{"ok": true, "device": {...}}`` and is printed only when every phase
passed. Without a TPU the script exits 3 before doing any work.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import tempfile
import time

DATA = os.path.join(tempfile.gettempdir(), "dmlc_tpu_chip_smoke.libsvm")
CORPUS_MB = 256
ROWS = 8192               # rows per batch
NNZ_BUCKET = ROWS * 45    # the generator writes < 45 features per row
NUM_FEATURES = 2 ** 20
STEPS = 20
GOLDEN_PREFIX = 16 << 20  # bytes of the corpus parsed by both engines
# Bounds for the f32 device steps against the NumPy float64 replay. On
# the v5e, f32 softplus (log1p(exp(-|m|))) is off by up to 1.06e-4
# absolute on [-4, 4], sigmoid by 1.2e-6 (measured, PR 21). The loss is
# a mean of softplus values, so its error is up to SOFTPLUS_EPS. JAX
# differentiates softplus as exp(m - softplus(m)), so each row's gradient
# is off by up to sigmoid(m) * SOFTPLUS_EPS: w relative to its own size,
# and b, the mean of those terms, by up to lr * SOFTPLUS_EPS per step.
SOFTPLUS_EPS = 2e-4
# Bounds for the sharded steps against the same steps on one chip: both
# f32 on the same chip kind, so only the order of the reductions
# differs. Observed on 4 v5e chips (PR 21, labels then alternating, b
# near 0): loss 3e-7, w 1.3e-7 of max |w|, b 1.7e-9; on 4 CPU devices
# with today's labels (|b| 0.38): 1.2e-6, 1e-7, 3e-8. A bias gradient
# left unsummed across chips would move b by about 1e-3.
SHARDED_LOSS_ATOL = 1e-5
SHARDED_W_RTOL = 2e-6     # of max |w|
SHARDED_B_ATOL = 1e-6


def check_steps(losses, params, ref_losses, ref_w, ref_b, loss_atol: float,
                w_rtol: float, b_atol: float) -> dict:
    """Assert the device's losses and parameters against a replay of
    the same steps; return the errors beside their bounds. The bias must
    have moved further than its bound, or a bias that never moved would
    pass too."""
    import numpy as np
    assert all(np.isfinite(losses)), f"non-finite loss: {losses}"
    w, b = np.asarray(params["w"]), float(params["b"])
    errs = {
        "loss_abs_err": float(np.abs(np.subtract(losses, ref_losses)).max()),
        "loss_abs_bound": loss_atol,
        "w_abs_err": float(np.abs(w - ref_w).max()),
        "w_abs_bound": w_rtol * float(np.abs(ref_w).max()),
        "b_abs_err": abs(b - float(ref_b)),
        "b_abs_bound": b_atol,
        "b_ref_abs": abs(float(ref_b)),
    }
    assert errs["b_ref_abs"] > b_atol, \
        f"the bias moved {errs['b_ref_abs']}, not past its bound {b_atol}"
    for k in ("loss_abs", "w_abs", "b_abs"):
        assert errs[f"{k}_err"] <= errs[f"{k}_bound"], \
            f"{k} error {errs[f'{k}_err']} over {errs[f'{k}_bound']}"
    return errs


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


class StreamHash:
    """Hash of a CSR row stream that does not depend on how the rows
    were cut into blocks or batches: one sha256 per column, fed in row
    order, with dtypes made canonical (the device keeps offsets as
    int32)."""

    COLUMNS = ("label", "weight", "length", "index", "value")

    def __init__(self):
        import numpy as np
        self._np = np
        self._h = {c: hashlib.sha256() for c in self.COLUMNS}
        self.rows = 0
        self.nnz = 0

    def add(self, label, weight, offset, index, value) -> None:
        np = self._np
        lengths = np.diff(np.asarray(offset, np.int64))
        cols = {"label": np.asarray(label, np.float32),
                "weight": np.asarray(weight, np.float32),
                "length": lengths,
                "index": np.asarray(index, np.uint64),
                "value": np.asarray(value, np.float32)}
        for c in self.COLUMNS:
            self._h[c].update(np.ascontiguousarray(cols[c]).tobytes())
        self.rows += len(lengths)
        self.nnz += int(lengths.sum())

    def add_padded(self, b) -> None:
        """One padded batch (host copy): its true rows only."""
        n, z = int(b["num_rows"]), int(b["num_nnz"])
        self.add(b["label"][:n], b["weight"][:n], b["offset"][:n + 1],
                 b["index"][:z], b["value"][:z])

    def add_block(self, blk) -> None:
        """One parsed RowBlock; absent weights and values read as 1, as
        the padded layout fills them."""
        np = self._np
        self.add(blk.label,
                 np.ones(blk.size) if blk.weight is None else blk.weight,
                 blk.offset, blk.index,
                 np.ones(blk.nnz) if blk.value is None else blk.value)

    def hexdigest(self) -> str:
        top = hashlib.sha256()
        for c in self.COLUMNS:
            top.update(self._h[c].digest())
        return top.hexdigest()


# -- phases (each callable on its own; tests run them on the CPU)

def host_stream_hash(path: str) -> StreamHash:
    """The file parsed on the host by the native engine."""
    from dmlc_tpu.data.parser import Parser
    h = StreamHash()
    p = Parser.create(path, 0, 1, format="libsvm", engine="native")
    for blk in p:
        h.add_block(blk)
    p.destroy()
    return h


def phase_ingest(path: str, dev, rows: int = ROWS,
                 nnz_bucket: int = NNZ_BUCKET):
    """One epoch of the Pipeline into ``dev``'s memory. Returns the
    device-resident batches, their host read-back, and the report."""
    import jax
    from dmlc_tpu.pipeline import Pipeline
    built = (Pipeline.from_uri(path)
             .parse(format="libsvm", engine="native")
             .batch(rows, pad=True, nnz_bucket=nnz_bucket)
             .to_device(dev)
             .build())
    t0 = time.perf_counter()
    batches = list(built)
    jax.block_until_ready(batches)
    epoch_s = time.perf_counter() - t0
    stages = built.stats()["stages"]
    built.close()
    assembly = next((x["assembly_path"] for s in stages
                     if (x := s.get("extra") or {}).get("assembly_path")),
                    None)
    assert assembly == "native-padded", \
        f"assembly_path {assembly!r}, want 'native-padded'"
    placed = {d for b in batches for a in b.values() for d in a.devices()}
    assert placed == {dev}, f"batches on {placed}, want {dev}"
    hbm_bytes = sum(a.nbytes for b in batches for a in b.values())
    host = jax.device_get(batches)
    hbm = StreamHash()
    for b in host:
        hbm.add_padded(b)
    ref = host_stream_hash(path)
    assert (hbm.rows, hbm.nnz) == (ref.rows, ref.nnz), \
        f"HBM holds {hbm.rows} rows/{hbm.nnz} nnz, file {ref.rows}/{ref.nnz}"
    assert hbm.hexdigest() == ref.hexdigest(), \
        "batches read back from HBM differ from the host parse"
    report = {"batches": len(batches), "rows": hbm.rows, "nnz": hbm.nnz,
              "hbm_bytes": hbm_bytes, "epoch_s": epoch_s,
              "assembly_path": assembly, "hbm_hash": hbm.hexdigest(),
              "host_hash": ref.hexdigest()}
    return batches, host, report


def phase_golden_parity(path: str, prefix_bytes: int = GOLDEN_PREFIX):
    """Native vs Python golden engine on the file's first part of at
    least ``prefix_bytes``: the CSR-byte-parity invariant."""
    import numpy as np
    from dmlc_tpu.data.parser import Parser
    from dmlc_tpu.data.rowblock import RowBlockContainer
    parts = max(1, os.path.getsize(path) // prefix_bytes)
    out = {}
    for engine in ("native", "python"):
        c = RowBlockContainer(np.uint32)
        p = Parser.create(path, 0, parts, format="libsvm", engine=engine)
        for blk in p:
            c.push_block(blk)
        if hasattr(p, "destroy"):
            p.destroy()
        blk = c.get_block()
        out[engine] = (blk.size, blk.nnz, blk.content_hash())
    assert out["native"] == out["python"], \
        f"native {out['native']} != python golden {out['python']}"
    rows, nnz, digest = out["native"]
    return {"part": f"0/{parts}",
            "bytes": os.path.getsize(path) // parts,
            "rows": rows, "nnz": nnz, "hash": digest}


def reference_sgd(host_batches, steps: int, num_features: int,
                  lr: float):
    """The same SGD steps as SparseLinearModel.train_step (weighted
    logistic loss, zero init, no l2), in NumPy float64."""
    import numpy as np
    w = np.zeros(num_features)
    b = 0.0
    losses = []
    for i in range(steps):
        hb = host_batches[i % len(host_batches)]
        off = np.asarray(hb["offset"], np.int64)
        nrow = len(hb["label"])
        z = int(off[-1])
        row = np.repeat(np.arange(nrow), np.diff(off))
        idx = np.asarray(hb["index"][:z], np.int64)
        val = np.asarray(hb["value"][:z], np.float64)
        y = (np.asarray(hb["label"]) > 0).astype(np.float64)
        wt = np.asarray(hb["weight"], np.float64)
        m = np.bincount(row, weights=val * w[idx], minlength=nrow) + b
        per_row = np.maximum(m, 0) - m * y + np.log1p(np.exp(-np.abs(m)))
        wsum = wt.sum()
        losses.append(float((per_row * wt).sum() / wsum))
        g = (1.0 / (1.0 + np.exp(-m)) - y) * wt / wsum
        w -= lr * np.bincount(idx, weights=g[row] * val,
                              minlength=num_features)
        b -= lr * g.sum()
    return losses, w, b


def phase_consumer(batches, host_batches, steps: int = STEPS,
                   num_features: int = NUM_FEATURES):
    """``steps`` train_step calls on the device-resident batches,
    checked against the float64 replay."""
    import jax
    import numpy as np
    from dmlc_tpu.models import SparseLinearModel
    model = SparseLinearModel(num_features)
    params = model.init_params()
    losses = []
    t0 = time.perf_counter()
    for i in range(steps):
        params, loss = model.train_step(params, batches[i % len(batches)])
        losses.append(loss)
        if i == 0:
            jax.block_until_ready(loss)
            first_s = time.perf_counter() - t0
    losses = [float(x) for x in jax.device_get(losses)]
    ref_losses, ref_w, ref_b = reference_sgd(
        host_batches, steps, num_features, model.learning_rate)
    errs = check_steps(losses, params, ref_losses, ref_w, ref_b,
                       SOFTPLUS_EPS, SOFTPLUS_EPS,
                       steps * model.learning_rate * SOFTPLUS_EPS)
    return {"steps": steps, "num_features": num_features,
            "first_step_s": first_s, "loss_first": losses[0],
            "loss_last": losses[-1], **errs}


def _sharded_stream(it, mesh):
    """Drain one epoch of a ShardedRowBlockIter: the global batches,
    and their content hashed per chip and joined in mesh order — the
    shard-parity invariant (part d's rows, d = 0..D-1, are the file)."""
    import numpy as np
    devs = list(mesh.devices.flat)
    per_dev = [[] for _ in devs]
    batches = []
    for gb in it:
        batches.append(gb)
        parts = [{} for _ in devs]
        for k in ("label", "weight", "offset", "index", "value",
                  "num_rows", "num_nnz"):
            shards = gb[k].addressable_shards
            held = {s.device for s in shards}
            assert held == set(devs), \
                f"batch[{k!r}] has shards on {sorted(map(str, held))}"
            for s in shards:
                parts[devs.index(s.device)][k] = np.asarray(s.data)[0]
        for d, part in enumerate(parts):
            per_dev[d].append(part)
    h = StreamHash()
    for parts in per_dev:
        for part in parts:
            h.add_padded(part)
    return batches, h


def phase_sharded(path: str, devices, steps: int = STEPS,
                  rows: int = ROWS, nnz_bucket: int = NNZ_BUCKET,
                  num_features: int = NUM_FEATURES):
    """The data-parallel path on a ``len(devices)``-chip mesh against
    one chip: stream hash, shard placement, losses and parameters."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh
    from dmlc_tpu.models import SparseLinearModel
    from dmlc_tpu.models.common import _weighted_mean
    from dmlc_tpu.parallel import ShardedRowBlockIter

    n = len(devices)
    mesh = Mesh(np.array(devices), ("data",))
    one = Mesh(np.array(devices[:1]), ("data",))
    kw = dict(format="libsvm", row_bucket=rows, nnz_bucket=nnz_bucket,
              engine="native")
    batches, h_n = _sharded_stream(ShardedRowBlockIter(path, mesh, **kw),
                                   mesh)
    _, h_1 = _sharded_stream(ShardedRowBlockIter(path, one, **kw), one)
    assert (h_n.rows, h_n.nnz) == (h_1.rows, h_1.nnz), \
        f"{n}-chip stream {h_n.rows}/{h_n.nnz}, 1-chip {h_1.rows}/{h_1.nnz}"
    assert h_n.hexdigest() == h_1.hexdigest(), \
        f"{n}-chip stream hash differs from the 1-chip stream"

    model = SparseLinearModel(num_features)
    step = model.make_sharded_train_step(mesh)
    params = model.init_params()
    losses = []
    t0 = time.perf_counter()
    for i in range(steps):
        params, loss = step(params, batches[i % len(batches)])
        losses.append(loss)
        if i == 0:
            jax.block_until_ready(loss)
            first_s = time.perf_counter() - t0
    losses = [float(x) for x in jax.device_get(losses)]

    # the same steps on one chip: the [n, ...] global batches on
    # devices[0], the per-block objective summed over the leading axis
    def one_chip_loss(p, batch):
        ls, ws = jax.vmap(lambda blk: model._block_objective(
            p, blk, blk["label"].shape[0]))(batch)
        return _weighted_mean(jnp.sum(ls), jnp.sum(ws))

    @jax.jit
    def one_chip_step(p, batch):
        loss, g = jax.value_and_grad(one_chip_loss)(p, batch)
        return jax.tree.map(lambda a, b: a - model.learning_rate * b,
                            p, g), loss

    keys = ("offset", "index", "value", "label", "weight")
    p1 = jax.device_put(model.init_params(), devices[0])
    losses1 = []
    for i in range(steps):
        gb = batches[i % len(batches)]
        hb = jax.device_put({k: np.asarray(jax.device_get(gb[k]))
                             for k in keys}, devices[0])
        p1, loss = one_chip_step(p1, hb)
        losses1.append(loss)
    losses1 = [float(x) for x in jax.device_get(losses1)]
    errs = check_steps(losses, params, losses1, np.asarray(p1["w"]),
                       float(p1["b"]), SHARDED_LOSS_ATOL, SHARDED_W_RTOL,
                       SHARDED_B_ATOL)
    return {"chips": n, "global_batches": len(batches),
            "rows": h_n.rows, "nnz": h_n.nnz,
            "stream_hash": h_n.hexdigest(),
            "one_chip_hash": h_1.hexdigest(),
            "shards_on": [str(d) for d in devices], "steps": steps,
            "first_step_s": first_s, "loss_first": losses[0],
            "loss_last": losses[-1], **errs}


def ensure_data(path: str = DATA, size_mb: int = CORPUS_MB) -> int:
    """Write a criteo-shaped libsvm corpus of ~size_mb MB to ``path``:
    a 4000-row block made from seed 0 and repeated, 25-44 features per
    row over a 10^6 index space, labels 1 with probability 1/4 (about
    Criteo's click rate, so a model's bias has a gradient to follow).
    Written anew on every call (well under a second for 256 MB), to a
    temp file renamed over ``path``: whatever was at ``path`` before,
    from this generator or another, is never reused."""
    import numpy as np
    want = size_mb << 20
    rng = np.random.default_rng(0)
    rows = []
    for _ in range(4000):
        nnz = int(rng.integers(25, 45))
        idx = np.sort(rng.choice(10 ** 6, nnz, replace=False))
        vals = rng.random(nnz)
        rows.append(" ".join(f"{j}:{v:.6f}" for j, v in zip(idx, vals)))
    labels = rng.random(len(rows)) < 0.25
    block = "".join(f"{int(y)} {r}\n" for y, r in zip(labels, rows)).encode()
    reps = max(1, want // len(block))
    tmp = f"{path}.tmp{os.getpid()}"
    with open(tmp, "wb") as f:
        for _ in range(reps):
            f.write(block)
    os.replace(tmp, path)
    return os.path.getsize(path)


def require_tpu():
    """The device to check, or exit 3: a run anywhere but on the chip
    is not this smoke test's run."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX found {dev.platform} ({dev}); refusing to "
              "run", file=sys.stderr, flush=True)
        sys.exit(3)
    return dev


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax

    dev = require_tpu()
    devices = jax.devices()
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 3
    devices = devices[:args.chips]

    from dmlc_tpu import native
    from dmlc_tpu.utils.compile_cache import cache_stats, place_compile_cache

    cache_dir = place_compile_cache()
    emit("device", platform=dev.platform, kind=dev.device_kind,
         count=len(devices), host_cores=os.cpu_count(),
         compile_cache=cache_dir)
    t = time.perf_counter()
    native.get_lib()  # builds from engine.cc on first use; fails loudly
    emit("native", build_or_load_s=time.perf_counter() - t)
    t = time.perf_counter()
    size = ensure_data(DATA, CORPUS_MB)
    emit("corpus", path=DATA, bytes=size, s=time.perf_counter() - t)
    if args.chips == 1:
        batches, host, report = phase_ingest(DATA, dev)
        emit("ingest", **report)
        emit("golden_parity", **phase_golden_parity(DATA))
        emit("consumer", **phase_consumer(batches, host))
    else:
        emit("sharded", **phase_sharded(DATA, devices))
    emit("compile", **cache_stats())
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
