"""End-to-end example: libfm ingest -> FM and field-aware FFM training.

The libfm format family closed into a loop: LibFMParser (reference:
src/data/libfm_parser.h) parses field:index:value text, and
SparseFMModel — the second-order FM that format family exists to feed —
trains on the resulting CSR batches under shard_map — followed by
SparseFFMModel, which additionally consumes the parsed field[] column
(fields flow text -> parser -> padded batch -> device). The training data
follows a pure INTERACTION rule (label = XOR over feature pairs), which
a linear model provably cannot fit and the FM's pairwise term can.

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

from dmlc_tpu.models import SparseFFMModel, SparseFMModel  # noqa: E402
from dmlc_tpu.parallel import ShardedRowBlockIter  # noqa: E402
from dmlc_tpu.io.tempdir import TemporaryDirectory  # noqa: E402

NPAIRS = 4
NCOL = 2 * NPAIRS + 2   # pair features + 2 context features
ROWS = 320
EPOCHS = 60


def make_libfm(path: str) -> None:
    """label = XOR(which side of a pair fired, context bit): zero linear
    signal by construction."""
    rng = np.random.RandomState(0)
    with open(path, "w") as f:
        for _ in range(ROWS):
            a, b, cbit = rng.randint(NPAIRS), rng.randint(2), rng.randint(2)
            feats = sorted({2 * a + b, 2 * NPAIRS + cbit})
            y = 1 if b == cbit else 0
            # field:index:value — field 0 = pair features, 1 = context
            # (plain FM ignores fields; the FFM below consumes them)
            toks = " ".join(
                f"{0 if j < 2 * NPAIRS else 1}:{j}:1" for j in feats)
            f.write(f"{y} {toks}\n")


def main() -> None:
    with TemporaryDirectory() as tmp:
        data = os.path.join(tmp.path, "train.libfm")
        make_libfm(data)

        mesh = Mesh(np.array(jax.devices()).reshape(-1), ("data",))
        print(f"mesh: {mesh.devices.size} devices on "
              f"{jax.devices()[0].platform}")

        it = ShardedRowBlockIter(data, mesh, format="libfm",
                                 row_bucket=64, nnz_bucket=256)
        batches = list(it)
        model = SparseFMModel(NCOL, num_factors=4, learning_rate=1.0)
        params = jax.device_put(model.init_params(seed=2))
        step = model.make_sharded_train_step(mesh)
        # field-aware FFM on the same batches: the field[] column the
        # libfm parser filled is consumed on device
        ffm = SparseFFMModel(NCOL, num_fields=2, num_factors=4,
                             learning_rate=1.0)
        fparams = jax.device_put(ffm.init_params(seed=2))
        fstep = ffm.make_sharded_train_step(mesh)

        ffm.validate_batch(batches[0])  # field ids fit num_fields

        # compile BOTH programs up front: on a starved shared host, a
        # multi-second XLA compile wedged between training loops can
        # stall one virtual device past the CPU collectives' rendezvous
        # timeout — front-loading the compiles keeps the loops' tiny
        # per-step executions as the only collective work
        _, loss0 = step(params, batches[0])
        _, f0 = fstep(fparams, batches[0])

        def train(step_fn, p, tag):
            for epoch in range(EPOCHS):
                for batch in batches:
                    p, loss = step_fn(p, batch)
                # per-epoch sync bounds the async dispatch backlog: on a
                # starved shared host, hundreds of queued 8-device
                # collectives can spread one collective's thread
                # arrivals past the CPU rendezvous watchdog
                loss = float(loss)
                if (epoch + 1) % 20 == 0:
                    print(f"{tag} epoch {epoch + 1}: loss {loss:.4f}")
            _, final = step_fn(p, batches[0])
            return float(final)

        loss1 = train(step, params, "FM")
        print(f"loss {float(loss0):.4f} -> {loss1:.4f} "
              f"(pure-interaction rule: a linear model stays ~0.69)")
        assert loss1 < 0.3, "FM failed to learn the XOR rule"

        f1 = train(fstep, fparams, "FFM")
        print(f"FFM: loss {float(f0):.4f} -> {f1:.4f} "
              f"(field[] parsed from text and consumed on device)")
        assert f1 < 0.3, "FFM failed to learn the XOR rule"
        print("OK")


if __name__ == "__main__":
    main()
