"""End-to-end example: libsvm-with-qid ingest -> pairwise ranking.

The qid column closed into a loop: the libsvm parser (reference:
src/data/libsvm_parser.h ``qid:`` tokens) fills RowBlock.qid, the
sharded ingest pads it (-1) into device batches, and SparseRankingModel
— the rank:pairwise objective that column exists to feed — trains under
shard_map. The data is query-grouped with graded relevance from a
hidden scorer, so pairwise accuracy provably rises.

Runs on whatever JAX finds; on the CPU (JAX_PLATFORMS=cpu) it uses 8
virtual devices.
"""

import os

# virtual devices for a CPU run (read at backend init; no effect on TPU)
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8")

import numpy as np  # noqa: E402
import jax  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from dmlc_tpu.models import SparseRankingModel  # noqa: E402
from dmlc_tpu.parallel import ShardedRowBlockIter  # noqa: E402
from dmlc_tpu.io.tempdir import TemporaryDirectory  # noqa: E402

NCOL = 32
NQUERIES = 64
DOCS_PER_Q = 6
EPOCHS = 40


def make_ranking_libsvm(path: str) -> None:
    """Query-grouped rows with graded labels (0/1/2) from a hidden
    linear scorer — the signal pairwise training should recover."""
    rng = np.random.RandomState(0)
    w_true = np.random.RandomState(7).randn(NCOL)
    with open(path, "w") as f:
        for q in range(NQUERIES):
            for _ in range(DOCS_PER_Q):
                nnz = rng.randint(3, 8)
                idx = np.sort(rng.choice(NCOL, nnz, replace=False))
                vals = rng.rand(nnz)
                score = float((vals * w_true[idx]).sum())
                grade = int(np.digitize(score, [0.6, 1.4]))
                feats = " ".join(f"{j}:{v:.4f}" for j, v in zip(idx, vals))
                f.write(f"{grade} qid:{q} {feats}\n")


def main() -> None:
    with TemporaryDirectory() as tmp:
        data = os.path.join(tmp.path, "train.libsvm")
        make_ranking_libsvm(data)

        mesh = Mesh(np.array(jax.devices()).reshape(-1), ("data",))
        print(f"mesh: {mesh.devices.size} devices on "
              f"{jax.devices()[0].platform}")

        # modest row bucket: the pairwise loss is O(row_bucket^2)
        it = ShardedRowBlockIter(data, mesh, format="libsvm",
                                 row_bucket=64, nnz_bucket=512)
        batches = list(it)
        model = SparseRankingModel(NCOL, learning_rate=1.0)
        model.validate_batch(batches[0])  # qid flowed to the device
        params = jax.device_put(model.init_params())
        step = model.make_sharded_train_step(mesh)

        # accuracy evaluated per device block (a flat concatenation
        # would need offsets rebuilt; the per-device view is exact)
        def accuracy(p):
            accs = []
            for b in batches:
                hb = {k: np.asarray(v) for k, v in b.items()}
                for d in range(hb["label"].shape[0]):
                    flat = {k: hb[k][d] for k in
                            ("offset", "index", "value", "label",
                             "weight", "qid")}
                    a = model.pairwise_accuracy(p, flat)
                    if np.isfinite(a):
                        accs.append(a)
            return float(np.mean(accs))

        acc0 = accuracy(jax.device_get(params))
        for epoch in range(EPOCHS):
            for batch in batches:
                params, loss = step(params, batch)
            loss = float(loss)  # per-epoch sync (see train_fm.py)
            if (epoch + 1) % 10 == 0:
                print(f"epoch {epoch + 1}: pairwise loss {loss:.4f}")
        acc1 = accuracy(jax.device_get(params))
        print(f"pairwise accuracy {acc0:.3f} -> {acc1:.3f} "
              f"(qid groups parsed from text, pairs formed on device)")
        assert acc1 > max(acc0, 0.8), (acc0, acc1)
        print("OK")


if __name__ == "__main__":
    main()
