"""Checkpoint subsystem + JSON utilities (reference: Serializable/Stream
checkpoint primitives + json.h; TPU-native sharded checkpoint)."""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dmlc_tpu.io.checkpoint import ShardedCheckpoint, load_pytree, save_pytree
from dmlc_tpu.io.stream import MemoryStream
from dmlc_tpu.utils.json_util import (
    JSONObjectReadHelper, json_dump, json_load, to_jsonable,
)
from dmlc_tpu.utils.logging import DMLCError


class TestJsonUtil:
    def test_roundtrip_with_numpy(self, rng):
        obj = {"a": 1, "b": [1.5, "x"], "arr": rng.rand(3, 2).astype(np.float32),
               "blob": b"\x00\x01", "n": np.int64(7)}
        s = MemoryStream()
        json_dump(obj, s)
        s.seek(0)
        out = json_load(s)
        assert out["a"] == 1 and out["b"] == [1.5, "x"] and out["n"] == 7
        np.testing.assert_array_equal(out["arr"], obj["arr"])
        assert out["blob"] == b"\x00\x01"

    def test_invalid_json(self):
        with pytest.raises(DMLCError, match="invalid JSON"):
            json_load(MemoryStream(b"{nope"))

    def test_object_helper(self):
        h = (JSONObjectReadHelper()
             .declare_field("name", str)
             .declare_field("size", int)
             .declare_field("opt", int, optional=True, default=3))
        out = h.read_all_fields({"name": "x", "size": 2})
        assert out == {"name": "x", "size": 2, "opt": 3}
        with pytest.raises(DMLCError, match="required"):
            h.read_all_fields({"name": "x"})
        with pytest.raises(DMLCError, match="unknown"):
            h.read_all_fields({"name": "x", "size": 1, "zz": 0})
        with pytest.raises(DMLCError, match="expected"):
            h.read_all_fields({"name": "x", "size": "two"})


class TestPytreeCheckpoint:
    def test_roundtrip_dict(self, tmp_path, rng):
        tree = {"w": rng.rand(8, 4).astype(np.float32),
                "opt": {"m": rng.rand(8).astype(np.float32)},
                "step": np.int64(17)}
        path = str(tmp_path / "ck.bin")
        save_pytree(tree, path)
        flat = load_pytree(path)
        np.testing.assert_array_equal(flat["w"], tree["w"])
        restored = load_pytree(path, like=tree)
        np.testing.assert_array_equal(restored["opt"]["m"], tree["opt"]["m"])
        assert restored["step"] == 17

    def test_jax_arrays(self, tmp_path):
        tree = {"w": jnp.arange(12.0).reshape(3, 4)}
        path = str(tmp_path / "j.bin")
        save_pytree(tree, path)
        out = load_pytree(path, like=tree)
        np.testing.assert_array_equal(np.asarray(out["w"]),
                                      np.asarray(tree["w"]))

    def test_missing_key_raises(self, tmp_path):
        save_pytree({"a": np.zeros(2)}, str(tmp_path / "c.bin"))
        with pytest.raises(DMLCError, match="missing"):
            load_pytree(str(tmp_path / "c.bin"), like={"b": np.zeros(2)})


class TestShardedCheckpoint:
    def make_sharded_tree(self):
        mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ("data",))
        sharding = NamedSharding(mesh, P("data"))
        x = jnp.arange(64.0, dtype=jnp.float32)
        xs = jax.device_put(x, sharding)
        w = jax.device_put(jnp.ones((5,), jnp.float32),
                           NamedSharding(mesh, P()))
        return {"x": xs, "w": w}, mesh

    def test_save_restore_sharded(self, tmp_path):
        tree, mesh = self.make_sharded_tree()
        ck = ShardedCheckpoint(str(tmp_path / "root"))
        d = ck.save(3, tree, metadata={"epoch": 1})
        assert os.path.exists(os.path.join(d, "COMMIT"))
        assert ck.latest_step() == 3
        restored, user = ck.restore(like=tree)
        assert user == {"epoch": 1}
        np.testing.assert_array_equal(np.asarray(restored["x"]),
                                      np.asarray(tree["x"]))
        assert restored["x"].sharding.is_equivalent_to(
            tree["x"].sharding, ndim=1)
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.asarray(tree["w"]))

    def test_multiple_steps_and_latest(self, tmp_path):
        tree, _ = self.make_sharded_tree()
        ck = ShardedCheckpoint(str(tmp_path / "r"))
        ck.save(1, tree)
        ck.save(5, tree)
        assert ck.all_steps() == [1, 5]
        assert ck.latest_step() == 5

    def test_uncommitted_not_restored(self, tmp_path):
        tree, _ = self.make_sharded_tree()
        ck = ShardedCheckpoint(str(tmp_path / "r"))
        d = ck.save(2, tree)
        os.remove(os.path.join(d, "COMMIT"))  # simulate torn save
        assert ck.latest_step() is None
        with pytest.raises(DMLCError, match="no committed"):
            ck.restore(like=tree)

    def test_restore_without_like(self, tmp_path):
        tree, _ = self.make_sharded_tree()
        ck = ShardedCheckpoint(str(tmp_path / "r"))
        ck.save(1, tree)
        flat, _ = ck.restore()
        np.testing.assert_array_equal(flat["x"], np.arange(64.0))

    def test_restore_sweeps_stale_spill_cache(self, tmp_path, rng):
        # restore marks a resume boundary: a replay spill file whose
        # source fingerprint no longer stats clean must be gone after
        # restore (the steady-replay mutation contract), while a cache
        # of an unchanged source survives
        from dmlc_tpu.data.row_iter import (
            RoundSpillWriter, default_spill_dir,
        )
        from dmlc_tpu.data.rowblock import RowBlockContainer
        src = tmp_path / "src.libsvm"
        src.write_bytes(b"1 1:1.0\n")
        st = os.stat(src)
        d = default_spill_dir()
        c = RowBlockContainer(np.uint32)
        c.push(1.0, [1], [1.0])
        blk = c.get_block()
        uniq = os.path.basename(str(tmp_path)).replace("_", "")
        stale = os.path.join(d, f"test-{uniq}-stale.pages")
        fresh = os.path.join(d, f"test-{uniq}-fresh.pages")
        for path, fp in (
                (stale, [[str(src), st.st_size + 1, st.st_mtime_ns]]),
                (fresh, [[str(src), st.st_size, st.st_mtime_ns]])):
            w = RoundSpillWriter(path, nparts=1,
                                 meta={"fingerprint": fp})
            w.add_row([blk])
            w.commit()
        try:
            tree, _ = self.make_sharded_tree()
            ck = ShardedCheckpoint(str(tmp_path / "r"))
            ck.save(1, tree)
            ck.restore(like=tree)
            assert not os.path.exists(stale), \
                "restore must sweep fingerprint-stale spill caches"
            assert os.path.exists(fresh), \
                "restore must keep caches of unchanged sources"
        finally:
            for p in (stale, fresh):
                if os.path.exists(p):
                    os.remove(p)


class TestCheckpointRegressions:
    def test_restore_without_like_replicated_and_scalar(self, tmp_path):
        mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ("data",))
        repl = NamedSharding(mesh, P())
        tree = {
            "x": jax.device_put(jnp.arange(64.0), NamedSharding(mesh, P("data"))),
            "w": jax.device_put(jnp.ones((5,), jnp.float32), repl),
            "b": jax.device_put(jnp.float32(2.5), repl),
        }
        ck = ShardedCheckpoint(str(tmp_path / "r"))
        ck.save(1, tree)
        flat, _ = ck.restore()
        np.testing.assert_array_equal(flat["x"], np.arange(64.0))
        np.testing.assert_array_equal(flat["w"], np.ones(5))  # not 8x dup
        assert flat["b"].shape == () and float(flat["b"]) == 2.5

    def test_replicated_leaf_written_once(self, tmp_path):
        mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ("data",))
        big = jax.device_put(jnp.zeros((1 << 16,), jnp.float32),
                             NamedSharding(mesh, P()))
        ck = ShardedCheckpoint(str(tmp_path / "r"))
        d = ck.save(1, {"big": big})
        shard_file = os.path.join(d, "shard-0.bin")
        size = os.path.getsize(shard_file)
        assert size < big.nbytes * 1.5  # one copy + framing, not 8 copies


class TestShardLocalRestore:
    """Restore must read only the placements intersecting the target
    sharding's addressable slices (VERDICT r1 #5): peak host memory ~
    local shard bytes, built via make_array_from_single_device_arrays."""

    def _tree(self, n=1 << 12):
        mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ("data",))
        sh = NamedSharding(mesh, P("data"))
        x = jax.device_put(jnp.arange(float(n), dtype=jnp.float32), sh)
        return {"x": x}, mesh, sh

    def test_restore_reads_only_needed_placements(self, tmp_path):
        tree, mesh, sh = self._tree()
        ck = ShardedCheckpoint(str(tmp_path / "r"))
        ck.save(1, tree)
        # single-process: all 8 devices are addressable, so the whole row
        # space is needed — the probe is that each placement is read
        # EXACTLY once (no full-file rescans, no per-device re-reads)
        restored, _ = ck.restore(like=tree)
        np.testing.assert_array_equal(np.asarray(restored["x"]),
                                      np.asarray(tree["x"]))
        # every byte read was a needed placement: total == stored bytes
        # of x exactly once (8 placements, no re-reads, no full-file scan)
        assert ck.last_restore_bytes_read <= tree["x"].nbytes + 8 * 64

    def test_accounting_scales_with_slice(self, tmp_path):
        # restore only x (sharded); a second huge leaf must NOT be read
        tree, mesh, sh = self._tree()
        big = jax.device_put(jnp.zeros((1 << 15,), jnp.float32),
                             NamedSharding(mesh, P()))
        full = {"x": tree["x"], "big": big}
        ck = ShardedCheckpoint(str(tmp_path / "r"))
        ck.save(1, full)
        restored, _ = ck.restore(like={"x": tree["x"]})
        np.testing.assert_array_equal(np.asarray(restored["x"]),
                                      np.asarray(tree["x"]))
        assert ck.last_restore_bytes_read < big.nbytes // 2, \
            "restore read leaves outside the requested tree"

    def test_reshard_on_restore(self, tmp_path):
        # stored on 8 devices, restored onto a 4-device mesh (placement-
        # driven assembly, mesh-topology independent)
        tree, mesh, sh = self._tree()
        ck = ShardedCheckpoint(str(tmp_path / "r"))
        ck.save(1, tree)
        mesh4 = Mesh(np.array(jax.devices()[:4]).reshape(4), ("data",))
        sh4 = NamedSharding(mesh4, P("data"))
        like = jax.device_put(jnp.zeros_like(np.asarray(tree["x"])), sh4)
        restored, _ = ck.restore(like={"x": like})
        np.testing.assert_array_equal(np.asarray(restored["x"]),
                                      np.asarray(tree["x"]))
        assert restored["x"].sharding.is_equivalent_to(sh4, ndim=1)

    def test_scalar_leaf_does_not_pull_full_model(self, tmp_path):
        # regression: an unsharded leaf (step counter) in `like` must not
        # trigger a full-model host assembly of the sharded leaves
        tree, mesh, sh = self._tree()
        full = {"x": tree["x"], "step": np.int64(7)}
        ck = ShardedCheckpoint(str(tmp_path / "r"))
        ck.save(1, full)
        restored, _ = ck.restore(like=full)
        np.testing.assert_array_equal(np.asarray(restored["x"]),
                                      np.asarray(tree["x"]))
        assert int(restored["step"]) == 7
        # x read exactly once + the scalar, not twice
        assert ck.last_restore_bytes_read <= tree["x"].nbytes + 1024

    def test_replicated_saved_to_sharded_target_reads_once(self, tmp_path):
        # regression: a replicated-SAVED leaf restored onto a sharded
        # target must read the single stored record once, not once per
        # device span (was 8x I/O)
        tree, mesh, _ = self._tree()
        repl = NamedSharding(mesh, P())
        w = jax.device_put(jnp.arange(4096.0, dtype=jnp.float32), repl)
        ck = ShardedCheckpoint(str(tmp_path / "r"))
        ck.save(1, {"w": w})
        sh = NamedSharding(mesh, P("data"))
        like = jax.device_put(jnp.zeros(4096, jnp.float32), sh)
        restored, _ = ck.restore(like={"w": like})
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.arange(4096.0))
        assert ck.last_restore_bytes_read <= w.nbytes + 1024

    def test_missing_index_file_still_restores(self, tmp_path):
        # regression: mixed indexed/unindexed shard files (version skew,
        # lost idx) must restore via the structural scan, and a stale
        # index whose bin_size mismatches is rejected in favor of a scan
        tree, mesh, sh = self._tree()
        ck = ShardedCheckpoint(str(tmp_path / "r"))
        d = ck.save(1, tree)
        idx = os.path.join(d, "shard-0.idx.json")
        os.remove(idx)  # simulate a pre-index writer / lost idx
        restored, _ = ck.restore(like=tree)
        np.testing.assert_array_equal(np.asarray(restored["x"]),
                                      np.asarray(tree["x"]))
        # stale index: wrong bin_size must be ignored, not trusted
        with open(idx, "w") as f:
            json.dump({"entries": [], "bin_size": 1}, f)
        ck2 = ShardedCheckpoint(str(tmp_path / "r"))
        restored2, _ = ck2.restore(like=tree)
        np.testing.assert_array_equal(np.asarray(restored2["x"]),
                                      np.asarray(tree["x"]))

    def test_resave_removes_stale_world_shards(self, tmp_path):
        # regression: re-saving a step must drop shard files from pids
        # outside the current world (elastic restart with fewer procs)
        # and invalidate COMMIT while rewriting
        tree, mesh, sh = self._tree()
        ck = ShardedCheckpoint(str(tmp_path / "r"))
        d = ck.save(1, tree)
        # plant shards from a departed pid 5 of a previous larger world
        with open(os.path.join(d, "shard-5.bin"), "wb") as f:
            f.write(b"stale")
        with open(os.path.join(d, "shard-5.idx.json"), "w") as f:
            json.dump({"entries": [], "bin_size": 5}, f)
        ck.save(1, tree)  # re-save same step, world=1
        assert not os.path.exists(os.path.join(d, "shard-5.bin"))
        assert not os.path.exists(os.path.join(d, "shard-5.idx.json"))
        restored, _ = ck.restore(like=tree)
        np.testing.assert_array_equal(np.asarray(restored["x"]),
                                      np.asarray(tree["x"]))

    def test_resave_replaces_data_and_cleans_up(self, tmp_path):
        tree, mesh, sh = self._tree()
        ck = ShardedCheckpoint(str(tmp_path / "r"))
        ck.save(1, tree)
        n = np.asarray(tree["x"]).shape[0]
        tree2 = {"x": jax.device_put(
            jnp.arange(float(n), dtype=jnp.float32) * 3, sh)}
        d = ck.save(1, tree2)
        restored, _ = ck.restore(like=tree)
        np.testing.assert_array_equal(np.asarray(restored["x"]),
                                      np.asarray(tree2["x"]))
        assert not os.path.isdir(d + ".new")
        assert not os.path.isdir(d + ".trash")

    def test_resave_crash_never_loses_committed(self, tmp_path):
        # ADVICE r2: a torn re-save (crash while writing the replacement)
        # must leave the previously committed step fully restorable —
        # the replacement builds in step-N.new and only swaps in once
        # committed
        tree, mesh, sh = self._tree()
        ck = ShardedCheckpoint(str(tmp_path / "r"))
        d = ck.save(1, tree)
        new = d + ".new"
        os.makedirs(new)
        with open(os.path.join(new, "shard-0.bin"), "wb") as f:
            f.write(b"torn re-save garbage")  # no COMMIT: crashed mid-write
        assert ck.latest_step() == 1
        restored, _ = ck.restore(like=tree)
        np.testing.assert_array_equal(np.asarray(restored["x"]),
                                      np.asarray(tree["x"]))
        # and a subsequent re-save recovers cleanly over the torn .new
        ck.save(1, tree)
        restored, _ = ck.restore(like=tree)
        np.testing.assert_array_equal(np.asarray(restored["x"]),
                                      np.asarray(tree["x"]))

    def test_interrupted_swap_serves_committed_new(self, tmp_path):
        # crash BETWEEN the swap's two renames: step dir missing, .new
        # fully committed — discovery and restore must serve the .new
        tree, mesh, sh = self._tree()
        ck = ShardedCheckpoint(str(tmp_path / "r"))
        d = ck.save(1, tree)
        os.rename(d, d + ".new")  # exactly the mid-swap on-disk state
        assert ck.latest_step() == 1
        assert ck.all_steps() == [1]
        restored, _ = ck.restore(like=tree)
        np.testing.assert_array_equal(np.asarray(restored["x"]),
                                      np.asarray(tree["x"]))

    def test_both_committed_serves_newer_new(self, tmp_path, monkeypatch):
        # ADVICE r4: crash BETWEEN .new's COMMIT and the swap renames
        # leaves BOTH step-1 and step-1.new committed. The .new is
        # provably the newer save (save() strips COMMIT from .new before
        # reuse) — restore must serve it, and keep serving the same data
        # after a later save promotes it (no flip-flop over time).
        tree, mesh, sh = self._tree()
        newer = {"x": np.asarray(tree["x"]) + 100.0}
        ck = ShardedCheckpoint(str(tmp_path / "r"))
        ck.save(1, tree)
        monkeypatch.setattr(ShardedCheckpoint, "_swap_in",
                            staticmethod(lambda final: None))
        ck.save(1, newer)  # commits .new, "crashes" before the swap
        monkeypatch.undo()
        restored, _ = ck.restore(like=tree)
        np.testing.assert_array_equal(np.asarray(restored["x"]),
                                      np.asarray(newer["x"]))
        # later activity elsewhere must not flip which copy step 1 means
        ck.save(2, tree)
        restored, _ = ck.restore(step=1, like=tree)
        np.testing.assert_array_equal(np.asarray(restored["x"]),
                                      np.asarray(newer["x"]))

    def test_save_over_interrupted_swap_crash_keeps_committed(
            self, tmp_path, monkeypatch):
        # r4 regression (code review): start from the mid-swap state
        # (step dir missing, .new fully committed — the step's ONLY
        # committed copy). A save of that step must NOT invalidate the
        # committed .new before a replacement exists: crash the save
        # during shard writing and the old data must still restore.
        tree, mesh, sh = self._tree()
        ck = ShardedCheckpoint(str(tmp_path / "r"))
        d = ck.save(1, tree)
        os.rename(d, d + ".new")  # exactly the mid-swap on-disk state
        bomb = RuntimeError("simulated crash mid shard write")

        def boom(leaf):
            raise bomb

        monkeypatch.setattr(ShardedCheckpoint, "_addressable_shards",
                            staticmethod(boom))
        with pytest.raises(RuntimeError):
            ck.save(1, {"x": np.zeros_like(np.asarray(tree["x"]))})
        monkeypatch.undo()
        assert ck.latest_step() == 1  # the old committed copy survived
        restored, _ = ck.restore(like=tree)
        np.testing.assert_array_equal(np.asarray(restored["x"]),
                                      np.asarray(tree["x"]))

    def test_replicated_target_restores(self, tmp_path):
        tree, mesh, _ = self._tree()
        ck = ShardedCheckpoint(str(tmp_path / "r"))
        ck.save(1, tree)
        repl = NamedSharding(mesh, P())
        like = jax.device_put(jnp.zeros_like(np.asarray(tree["x"])), repl)
        restored, _ = ck.restore(like={"x": like})
        np.testing.assert_array_equal(np.asarray(restored["x"]),
                                      np.asarray(tree["x"]))
        # 8 replicated devices share one assembled slice (cache), so the
        # stored data is read once, not 8 times
        assert ck.last_restore_bytes_read <= tree["x"].nbytes + 8 * 64


class TestRemoteCheckpoint:
    """Device-direct sharded checkpoint on an ``obj://`` root: pages
    stream through the objstore write plane, saves are incremental by
    content digest, COMMIT gates restorability, and restore verifies
    every page against its digest."""

    @pytest.fixture
    def remote(self, tmp_path, monkeypatch):
        import dmlc_tpu.io.objstore as objstore
        import dmlc_tpu.io.objstore.fs as ofs
        import dmlc_tpu.io.pagestore as ps
        from dmlc_tpu.io.objstore.emulator import EmulatedObjectStore
        monkeypatch.delenv(ofs.ENV_ROOT, raising=False)
        monkeypatch.setattr(ps, "default_store_dir",
                            lambda: str(tmp_path / "pagestore"))
        saved = ofs.options()
        em = EmulatedObjectStore(str(tmp_path / "objroot"))
        objstore.configure(em)
        yield em
        objstore.configure(
            None, block_bytes=saved["block_bytes"],
            coalesce=saved["coalesce"], parallel=saved["parallel"],
            hydrate=saved["hydrate"],
            put_part_bytes=saved["put_part_bytes"],
            put_parallel=saved["put_parallel"])

    def _tree(self, rng, scale=1.0):
        return {"w": (rng.rand(256, 16) * scale).astype(np.float32),
                "b": rng.rand(64).astype(np.float32),
                "step": np.int64(7)}

    def test_save_restore_roundtrip(self, remote, rng):
        tree = self._tree(rng)
        ck = ShardedCheckpoint("obj://b/ck")
        d = ck.save(3, tree, metadata={"epoch": 2})
        assert d == "obj://b/ck/step-00000003"
        assert ck.last_save_bytes_written > 0
        assert ck.latest_step() == 3 and ck.all_steps() == [3]
        restored, user = ck.restore(like=tree)
        assert user == {"epoch": 2}
        np.testing.assert_array_equal(restored["w"], tree["w"])
        np.testing.assert_array_equal(restored["b"], tree["b"])
        assert restored["step"] == 7
        assert ck.last_restore_bytes_read > 0

    def test_incremental_save_reuses_unchanged_pages(self, remote, rng):
        tree = self._tree(rng)
        ck = ShardedCheckpoint("obj://b/ck")
        ck.save(1, tree)
        first = ck.last_save_bytes_written
        assert first > 0 and ck.last_save_bytes_reused == 0
        tree2 = dict(tree, b=(tree["b"] + 1.0))  # one small leaf moves
        ck.save(2, tree2)
        # only the changed leaf uploads; the big unchanged pages dedup
        assert ck.last_save_bytes_reused > 0
        assert 0 < ck.last_save_bytes_written < first // 4
        for step, want in ((1, tree), (2, tree2)):
            got, _ = ck.restore(step=step, like=tree)
            np.testing.assert_array_equal(got["w"], want["w"])
            np.testing.assert_array_equal(got["b"], want["b"])

    def test_same_tree_resave_uploads_nothing(self, remote, rng):
        tree = self._tree(rng)
        ck = ShardedCheckpoint("obj://b/ck")
        ck.save(1, tree)
        ck.save(2, tree)
        assert ck.last_save_bytes_written == 0
        assert ck.last_save_bytes_reused > 0

    def test_uncommitted_step_not_restorable(self, remote, rng):
        tree = self._tree(rng)
        ck = ShardedCheckpoint("obj://b/ck")
        ck.save(4, tree)
        remote.delete("b", "ck/step-00000004/COMMIT")  # torn save
        assert ck.latest_step() is None
        with pytest.raises(DMLCError, match="no committed"):
            ck.restore(like=tree)
        with pytest.raises(DMLCError, match="not committed"):
            ck.restore(step=4, like=tree)

    def test_multi_writer_gang_save(self, remote, rng):
        """Two writers with DISJOINT leaves converge on one committed
        step: writer 1 publishes its shard index first, writer 0
        commits only after seeing every index."""
        t0 = {"w0": rng.rand(32, 8).astype(np.float32)}
        t1 = {"w1": rng.rand(16, 4).astype(np.float32)}
        ck = ShardedCheckpoint("obj://b/ck")
        ck.save(9, t1, writer=1, num_writers=2)   # no COMMIT yet
        assert ck.latest_step() is None
        ck.save(9, t0, writer=0, num_writers=2)   # commits
        assert ck.latest_step() == 9
        like = {"w0": t0["w0"], "w1": t1["w1"]}
        restored, _ = ck.restore(like=like)
        np.testing.assert_array_equal(restored["w0"], t0["w0"])
        np.testing.assert_array_equal(restored["w1"], t1["w1"])

    def test_writer_args_rejected_on_local_root(self, tmp_path, rng):
        ck = ShardedCheckpoint(str(tmp_path / "local"))
        with pytest.raises(DMLCError, match="remote"):
            ck.save(1, self._tree(rng), writer=0, num_writers=2)

    def test_corrupt_page_detected(self, remote, rng, tmp_path,
                                   monkeypatch):
        import dmlc_tpu.io.pagestore as ps
        tree = self._tree(rng)
        ck = ShardedCheckpoint("obj://b/ck")
        ck.save(1, tree)
        # corrupt ONE page object in place (valid serialized ndarray,
        # wrong content), and point at a fresh page store so restore
        # must take the wire and verify the digest
        pages = os.path.join(remote.root, "b", "ck", "pages")
        name = sorted(os.listdir(pages))[0]
        with open(os.path.join(pages, name), "r+b") as f:
            raw = bytearray(f.read())
            raw[-4] ^= 0xFF  # flip payload bytes near the tail
            f.seek(0)
            f.write(raw)
        monkeypatch.setattr(ps, "default_store_dir",
                            lambda: str(tmp_path / "pagestore2"))
        with pytest.raises(DMLCError, match="content mismatch"):
            ck.restore(like=tree)

    def test_restore_split_accounting(self, remote, rng, tmp_path,
                                      monkeypatch):
        import dmlc_tpu.io.pagestore as ps
        tree = self._tree(rng)
        ck = ShardedCheckpoint("obj://b/ck")
        ck.save(1, tree)
        # same process: the saver's page store answers everything
        ck.restore(like=tree)
        assert ck.last_restore_local_bytes == ck.last_restore_bytes_read
        assert ck.last_restore_wire_bytes == 0
        # a cold process (fresh page store) pays the wire — no gang,
        # so the whole checkpoint is wire bytes
        monkeypatch.setattr(ps, "default_store_dir",
                            lambda: str(tmp_path / "pagestore2"))
        ck2 = ShardedCheckpoint("obj://b/ck")
        ck2.restore(like=tree)
        assert ck2.last_restore_wire_bytes == ck2.last_restore_bytes_read
        assert ck2.last_restore_bytes_read > 0

    def test_different_world_restore_fans_out_over_peers(
            self, remote, tmp_path, monkeypatch):
        """A three-writer save restored by a two-rank gang: each rank
        wire-fetches only the pages ``content_owner`` assigns it at
        world 2 and takes the rest from its peer's ``/pages`` tier.
        Seen from rank 0, with rank 1 staged and served in-process:
        every leaf restores identical, nothing counts as local, and
        the bytes split between the peer and the wire, the wire
        carrying at most 0.60 of them."""
        import dmlc_tpu.io.pagestore as ps
        from dmlc_tpu.io.objstore import peer as peer_mod
        from dmlc_tpu.obs.serve import StatusServer
        trees = [{f"w{w}": {f"l{i}": np.random.RandomState(100 * w + i)
                            .rand(2048).astype(np.float32)
                            for i in range(8)}} for w in range(3)]
        saver = ShardedCheckpoint("obj://b/ck")
        for w in (1, 2, 0):  # the committing writer saves last
            saver.save(5, trees[w], writer=w, num_writers=3)
        stores = [str(tmp_path / f"rank{r}") for r in range(2)]
        for d in stores:
            os.makedirs(d)
        servers = [StatusServer(pages_root=d) for d in stores]
        ports = [srv.port for srv in servers]
        try:
            # rank 1 stages its cut of the pages first
            monkeypatch.setattr(ps, "default_store_dir",
                                lambda: stores[1])
            peer_mod.configure(ports=ports, self_port=ports[1])
            ShardedCheckpoint("obj://b/ck").prefetch()
            # rank 0: its own cut from the wire, the rest from rank 1
            monkeypatch.setattr(ps, "default_store_dir",
                                lambda: stores[0])
            peer_mod.configure(ports=ports, self_port=ports[0])
            ck = ShardedCheckpoint("obj://b/ck")
            ck.prefetch()
            host, _ = ck.restore(like=None)
        finally:
            peer_mod.reset()
            for srv in servers:
                srv.close()
        want = {f"w{w}/{k}": a for w in range(3)
                for k, a in trees[w][f"w{w}"].items()}
        assert sorted(host) == sorted(want)
        for k, a in want.items():
            np.testing.assert_array_equal(np.asarray(host[k]), a)
        assert ck.last_restore_local_bytes == 0
        assert ck.last_restore_peer_bytes > 0
        # rank 0 owns about half the pages at world 2
        assert 0 < ck.last_restore_wire_bytes \
            <= 0.60 * ck.last_restore_bytes_read
        assert (ck.last_restore_peer_bytes + ck.last_restore_wire_bytes
                == ck.last_restore_bytes_read)

    def test_sharded_jax_tree_remote(self, remote):
        tree, _ = TestShardedCheckpoint().make_sharded_tree()
        ck = ShardedCheckpoint("obj://b/ck")
        ck.save(2, tree)
        restored, _ = ck.restore(like=tree)
        np.testing.assert_array_equal(np.asarray(restored["x"]),
                                      np.asarray(tree["x"]))
        assert restored["x"].sharding.is_equivalent_to(
            tree["x"].sharding, ndim=1)


class TestAnalyzeRestoreEvidence:
    def test_evidence_names_fanout_split_rates(self):
        from dmlc_tpu.obs.analyze import attribute
        snap = {"wall_s": 2.0,
                "stages": [{"name": "parse", "kind": "parse",
                            "wait_s": 1.5, "bytes": 1_000_000_000}]}
        metrics = {"counters": {
            "checkpoint.restore_bytes": 900_000_000,
            "checkpoint.restore.local_bytes": 100_000_000,
            "checkpoint.restore.peer_bytes": 500_000_000,
            "checkpoint.restore.wire_bytes": 300_000_000}}
        v = attribute(snap, metrics=metrics)
        lines = [e for e in v["evidence"]
                 if e.startswith("checkpoint restore:")]
        assert len(lines) == 1
        assert "900000000 bytes" in lines[0]
        assert "100000000 local" in lines[0]
        assert "500000000 peer-served" in lines[0]
        assert "300000000 wire" in lines[0]
        assert "GB/s peer-served" in lines[0]
        assert "GB/s wire-served" in lines[0]

    def test_no_restore_no_evidence_line(self):
        from dmlc_tpu.obs.analyze import attribute
        snap = {"wall_s": 2.0,
                "stages": [{"name": "parse", "kind": "parse",
                            "wait_s": 1.5, "bytes": 1_000_000_000}]}
        v = attribute(snap, metrics={"counters": {}})
        assert not [e for e in v["evidence"]
                    if e.startswith("checkpoint restore:")]
