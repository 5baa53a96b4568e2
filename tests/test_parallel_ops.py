"""Sharded ingest + CSR device ops + model training on the 8-device
CPU mesh (the multi-chip contract, SURVEY.md §5.8)."""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from dmlc_tpu.data.parser import Parser
from dmlc_tpu.data.rowblock import RowBlock, RowBlockContainer
from dmlc_tpu.models import SparseFMModel, SparseLinearModel
from jax.extend import core as jcore

from dmlc_tpu.ops import (
    csr_row_ids, csr_row_sum, csr_to_dense, csr_to_padded_rows, sdot_rows,
    segment_spmv, sharded_spmv, spmv,
)
from dmlc_tpu.parallel import (
    DeviceIter, ShardedRowBlockIter, device_prefetch, empty_block,
    ensure_schema, make_global_batch, next_pow2_bucket, pad_to_bucket,
    stack_device_batches,
)


@pytest.fixture(scope="module")
def mesh():
    return Mesh(np.array(jax.devices()[:8]).reshape(8), ("data",))


def random_block(rng, rows=64, ncol=50, max_nnz=8):
    c = RowBlockContainer(np.uint32)
    for i in range(rows):
        nnz = rng.randint(0, max_nnz)
        idx = np.sort(rng.choice(ncol, nnz, replace=False))
        c.push(float(rng.randint(0, 2) * 2 - 1), idx,
               rng.rand(nnz).astype(np.float32))
    return c.get_block()


def _offsets(lengths, nnz=None):
    """CSR offsets for rows of the given lengths; nnz defaults to the
    total (no padded tail)."""
    offset = np.concatenate([[0], np.cumsum(lengths)])
    return offset, int(offset[-1] if nnz is None else nnz)


ROW_ID_CASES = {
    # rows of 0..7 nonzeros: empty rows repeat an offset
    "empty_rows": lambda rng: _offsets(rng.randint(0, 8, size=64), 512),
    "padded_tail": lambda rng: _offsets([3, 0, 5, 2], 64),
    "nnz_equals_last_offset": lambda rng: _offsets([4, 1, 0, 6, 2]),
    "one_row": lambda rng: _offsets([9], 16),
    "all_padded": lambda rng: _offsets(np.zeros(32, np.int64), 128),
    "more_rows_than_nnz": lambda rng: _offsets(
        (rng.rand(200) < 0.05).astype(np.int64), 16),
    # nnz shorter than offset[-1]: offsets past nnz are never <= k
    "offsets_past_nnz": lambda rng: _offsets([5, 5, 5], 8),
}


ROW_SUM_CASES = {
    "empty_first_middle_last": lambda rng: _offsets([0, 3, 0, 0, 5, 2, 0]),
    "all_empty": lambda rng: _offsets(np.zeros(16, np.int64), 128),
    "no_nonzeros": lambda rng: _offsets(np.zeros(5, np.int64)),
    # indices past the rows stay nonzero: only value 0 makes them neutral
    "padded_tail": lambda rng: _offsets([3, 4, 1], 200),
    "single_nonzero_rows": lambda rng: _offsets(np.ones(50, np.int64)),
    # runs across one and across nine 128-lane rows
    "long_rows": lambda rng: _offsets([130, 3, 1100, 0, 7], 1300),
    "nnz_not_lane_multiple": lambda rng: _offsets(rng.randint(0, 8, 40),
                                                  333),
    "criteo_like": lambda rng: _offsets(np.full(64, 39), 64 * 39 + 64),
}


def _nnz_indexed(jaxpr, nnz):
    """Gathers and scatters, in ``jaxpr`` and every jaxpr inside it,
    whose index operand has ``nnz`` entries."""
    count = 0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name.startswith(("gather", "scatter")):
            count += eqn.invars[1].aval.shape[0] == nnz
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                if isinstance(sub, jcore.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jcore.Jaxpr):
                    count += _nnz_indexed(sub, nnz)
    return count


class TestCsrOps:
    @pytest.mark.parametrize("x64", [False, True], ids=["int32", "int64"])
    @pytest.mark.parametrize("case", sorted(ROW_SUM_CASES))
    def test_row_sums_match_float64(self, rng, case, x64):
        """csr_row_sum and segment_spmv, values and their gradients with
        respect to the weights and the values, against NumPy float64."""
        offset, nnz = ROW_SUM_CASES[case](rng)
        n, ncol = len(offset) - 1, 50
        index = rng.randint(1, ncol, nnz).astype(np.uint32)
        value = rng.randn(nnz).astype(np.float32)
        value[offset[-1]:] = 0
        w = rng.randn(ncol).astype(np.float32)
        cot = rng.randn(n).astype(np.float32)
        rows = np.searchsorted(offset, np.arange(nnz), side="right") - 1
        live = rows < n
        terms = value.astype(np.float64) * w[index]
        gold = np.bincount(rows[live], terms[live], minlength=n)
        scale = np.bincount(rows[live], np.abs(terms[live]), minlength=n)
        row_cot = np.where(live, cot[np.minimum(rows, n - 1)], 0.0)
        gold_dvalue = row_cot * w[index]
        gold_dw = np.bincount(index, row_cot * value, minlength=ncol)
        dw_scale = np.bincount(index, np.abs(row_cot * value),
                               minlength=ncol)
        dtype = np.int64 if x64 else np.int32
        with jax.enable_x64(x64):
            off = jnp.asarray(offset.astype(dtype))
            assert off.dtype == dtype
            y, pull = jax.vjp(
                lambda v, wt: segment_spmv(off, jnp.asarray(index), v, wt,
                                           num_rows=n),
                jnp.asarray(value), jnp.asarray(w))
            dvalue, dw = pull(jnp.asarray(cot))
            sums, pull = jax.vjp(jax.jit(lambda c: csr_row_sum(off, c, n)),
                                 jnp.asarray(terms.astype(np.float32)))
            (dterms,) = pull(jnp.asarray(cot))
        eps = np.finfo(np.float32).eps
        for got in (y, sums):
            assert got.dtype == np.float32
            assert np.all(np.abs(np.asarray(got) - gold)
                          <= 16 * eps * scale + 1e-30)
        np.testing.assert_allclose(np.asarray(dvalue), gold_dvalue,
                                   rtol=4 * eps, atol=0)
        np.testing.assert_array_equal(np.asarray(dterms), row_cot)
        assert np.all(np.abs(np.asarray(dw) - gold_dw)
                      <= 16 * eps * dw_scale + 1e-30)

    def test_row_sums_index_only_the_offsets(self):
        """The step's only nnz-long gather and scatter are on the weight
        side (the weight gather, the gradient's scatter-add): the row
        side indexes the n + 1 offsets alone."""
        rows, nnz, ncol = 16, 160, 50
        batch = {"offset": jnp.zeros(rows + 1, jnp.int32),
                 "label": jnp.zeros(rows, jnp.float32),
                 "weight": jnp.zeros(rows, jnp.float32),
                 "index": jnp.zeros(nnz, jnp.uint32),
                 "value": jnp.zeros(nnz, jnp.float32)}
        model = SparseLinearModel(ncol)
        jaxpr = jax.make_jaxpr(jax.value_and_grad(model.loss))(
            model.init_params(), batch)
        assert _nnz_indexed(jaxpr.jaxpr, nnz) == 2

    @pytest.mark.parametrize("x64", [False, True], ids=["int32", "int64"])
    @pytest.mark.parametrize("case", sorted(ROW_ID_CASES))
    def test_csr_row_ids_matches_searchsorted(self, rng, case, x64):
        offset, nnz = ROW_ID_CASES[case](rng)
        gold = np.searchsorted(offset, np.arange(nnz), side="right") - 1
        dtype = np.int64 if x64 else np.int32
        with jax.enable_x64(x64):
            off = jnp.asarray(offset.astype(dtype))
            assert off.dtype == dtype
            eager = csr_row_ids(off, nnz)
            jitted = jax.jit(csr_row_ids, static_argnums=1)(off, nnz)
        assert jnp.issubdtype(eager.dtype, jnp.integer)
        np.testing.assert_array_equal(np.asarray(eager), gold)
        np.testing.assert_array_equal(np.asarray(jitted), gold)

    def test_row_ids_lower_without_a_loop(self):
        """A serial search (searchsorted lowers to a while loop) must not
        come back into the step: lowering only, no device needed."""
        rows, nnz, ncol = 16, 128, 50
        batch = {"offset": jnp.zeros(rows + 1, jnp.int32),
                 "label": jnp.zeros(rows, jnp.float32),
                 "weight": jnp.zeros(rows, jnp.float32),
                 "index": jnp.zeros(nnz, jnp.uint32),
                 "value": jnp.zeros(nnz, jnp.float32)}
        spmv_ir = segment_spmv.lower(
            batch["offset"], batch["index"], batch["value"],
            jnp.zeros(ncol, jnp.float32), num_rows=rows).as_text()
        model = SparseLinearModel(ncol)
        step_ir = SparseLinearModel.train_step.lower(
            model, model.init_params(), batch).as_text()
        for ir in (spmv_ir, step_ir):
            assert "stablehlo.while" not in ir

    def test_spmv_matches_numpy(self, rng):
        block = random_block(rng)
        w = rng.rand(50).astype(np.float32)
        y = spmv(block.offset, block.index, block.value, w)
        gold = np.array([row.sdot(w) for row in block], np.float32)
        np.testing.assert_allclose(np.asarray(y), gold, rtol=1e-5)

    def test_spmv_with_padding_neutral(self, rng):
        block = random_block(rng, rows=10)
        padded = pad_to_bucket(block, 16, 128)
        y = segment_spmv(jnp.asarray(padded["offset"]),
                         jnp.asarray(padded["index"]),
                         jnp.asarray(padded["value"]),
                         jnp.ones(50, jnp.float32), num_rows=16)
        gold = np.zeros(16, np.float32)
        for i, row in enumerate(block):
            gold[i] = row.sdot(np.ones(50, np.float32))
        np.testing.assert_allclose(np.asarray(y), gold, rtol=1e-5)

    def test_csr_to_dense(self, rng):
        block = random_block(rng, rows=7, ncol=9)
        dense = csr_to_dense(jnp.asarray(block.offset),
                             jnp.asarray(block.index),
                             jnp.asarray(block.value), 7, 9)
        gold = np.zeros((7, 9), np.float32)
        for i, row in enumerate(block):
            for j in range(row.length):
                gold[i, int(row.index[j])] += float(row.value[j])
        np.testing.assert_allclose(np.asarray(dense), gold, rtol=1e-6)

    def test_padded_rows_sdot(self, rng):
        block = random_block(rng, rows=12)
        pi, pv, mask = csr_to_padded_rows(block.offset, block.index,
                                          block.value)
        w = rng.rand(50).astype(np.float32)
        y = sdot_rows(pi, pv, w)
        gold = np.array([row.sdot(w) for row in block], np.float32)
        np.testing.assert_allclose(np.asarray(y), gold, rtol=1e-5)
        assert mask.sum() == block.nnz


class TestPadAndStack:
    def test_pad_contract(self, rng):
        block = random_block(rng, rows=5)
        out = pad_to_bucket(block, 8, 64)
        assert out["offset"].shape == (9,)
        assert out["label"].shape == (8,)
        assert out["index"].shape == (64,)
        assert out["num_rows"] == 5
        # padded rows empty + weight 0
        assert out["offset"][5] == out["offset"][8] == block.nnz
        assert (out["weight"][5:] == 0).all()
        assert (out["value"][block.nnz:] == 0).all()

    def test_bucket_too_small(self, rng):
        block = random_block(rng, rows=5)
        with pytest.raises(Exception):
            pad_to_bucket(block, 2, 64)

    def test_next_pow2(self):
        assert next_pow2_bucket(5) == 8
        assert next_pow2_bucket(8) == 8
        assert next_pow2_bucket(9) == 16
        assert next_pow2_bucket(0) == 8

    def test_stack(self, rng):
        blocks = [pad_to_bucket(random_block(rng, rows=4), 8, 64)
                  for _ in range(3)]
        stacked = stack_device_batches(blocks)
        assert stacked["label"].shape == (3, 8)
        assert stacked["num_rows"].shape == (3,)

    def test_fused_stack_matches_composed_path(self, rng):
        # stack_padded_rows is the replay serve-thread hot loop: it must
        # be BYTE-identical to pad_to_bucket + ensure_schema +
        # stack_device_batches on every column combination (plain,
        # qid-bearing, field-bearing, weighted, empty pads, forced keys)
        from dmlc_tpu.parallel.sharded import stack_padded_rows

        def qid_block(rows):
            c = RowBlockContainer(np.uint32)
            for i in range(rows):
                nnz = rng.randint(1, 5)
                idx = np.sort(rng.choice(50, nnz, replace=False))
                c.push(float(i % 3), idx, rng.rand(nnz), qid=i // 2,
                       weight=0.5 + rng.rand())
            return c.get_block()

        def field_block(rows):
            c = RowBlockContainer(np.uint32)
            for i in range(rows):
                nnz = rng.randint(1, 5)
                idx = np.sort(rng.choice(50, nnz, replace=False))
                c.push(float(i % 2), idx, rng.rand(nnz),
                       fields=rng.randint(0, 4, nnz))
            return c.get_block()

        cases = [
            ([random_block(rng, rows=5), random_block(rng, rows=3),
              empty_block()], False, False),
            ([qid_block(4), empty_block(), random_block(rng, rows=2)],
             True, False),
            ([field_block(3), empty_block()], False, True),
            ([random_block(rng, rows=2)], True, True),  # forced keys
        ]
        for blocks, want_qid, want_field in cases:
            fused = stack_padded_rows(blocks, 8, 64, want_qid, want_field)
            composed = stack_device_batches(
                [ensure_schema(pad_to_bucket(b, 8, 64), 8, 64,
                               want_qid
                               or any(x.qid is not None for x in blocks),
                               want_field
                               or any(x.field is not None
                                      for x in blocks))
                 for b in blocks])
            assert set(fused) == set(composed)
            for k in fused:
                assert fused[k].dtype == composed[k].dtype, k
                np.testing.assert_array_equal(fused[k], composed[k],
                                              err_msg=k)


class TestGlobalBatch:
    def test_sharding_layout(self, mesh, rng):
        locals_ = [pad_to_bucket(random_block(rng, rows=4), 8, 64)
                   for _ in range(8)]
        gb = make_global_batch(stack_device_batches(locals_), mesh)
        assert gb["offset"].shape == (8, 9)
        assert gb["offset"].sharding.spec == P("data", None)
        assert len(gb["offset"].addressable_shards) == 8

    def test_sharded_spmv_matches_local(self, mesh, rng):
        blocks = [random_block(rng, rows=6) for _ in range(8)]
        locals_ = [pad_to_bucket(b, 8, 64) for b in blocks]
        gb = make_global_batch(stack_device_batches(locals_), mesh)
        w = rng.rand(50).astype(np.float32)
        y = sharded_spmv(gb, w, mesh)
        assert y.shape == (8, 8)
        for d, b in enumerate(blocks):
            gold = np.array([row.sdot(w) for row in b], np.float32)
            np.testing.assert_allclose(np.asarray(y)[d, :b.size], gold,
                                       rtol=1e-5)


class TestShardedRowBlockIter:
    def test_coverage_across_devices(self, mesh, tmp_path, rng):
        lines = [f"{i % 2} {rng.randint(0, 50)}:{rng.rand():.6f}".encode()
                 for i in range(333)]
        p = tmp_path / "d.libsvm"
        p.write_bytes(b"\n".join(lines) + b"\n")
        it = ShardedRowBlockIter(str(p), mesh, format="libsvm",
                                 row_bucket=32, nnz_bucket=64,
                                 prefetch=False)
        total = 0
        for gb in it:
            total += int(np.asarray(gb["num_rows"]).sum())
            assert gb["label"].sharding.spec == P("data", None)
        assert total == 333

    def test_empty_block_padding(self):
        b = empty_block()
        assert b.size == 0 and b.nnz == 0
        padded = pad_to_bucket(b, 4, 16)
        assert (padded["weight"] == 0).all()

    @staticmethod
    def _write_libsvm(path, rng, n):
        lines = [f"{i % 2} {rng.randint(0, 50)}:{rng.rand():.6f}".encode()
                 for i in range(n)]
        path.write_bytes(b"\n".join(lines) + b"\n")

    def _collect(self, it):
        out = []
        for gb in it:
            out.append({k: np.asarray(v) for k, v in gb.items()})
        return out

    def test_over_budget_fallback_matches_cached_path(self, mesh, tmp_path,
                                                      rng):
        # agreement_cache_bytes=0 forces the legacy per-round protocol;
        # the batch stream must be identical to the cached fast path
        p = tmp_path / "d.libsvm"
        self._write_libsvm(p, rng, 200)
        kw = dict(format="libsvm", row_bucket=32, nnz_bucket=64,
                  prefetch=False)
        fast = self._collect(ShardedRowBlockIter(
            str(p), mesh, first_epoch_cache="always", **kw))
        slow = self._collect(ShardedRowBlockIter(
            str(p), mesh, first_epoch_cache="always",
            agreement_cache_bytes=0, **kw))
        assert len(fast) == len(slow) > 0
        for a, b in zip(fast, slow):
            assert set(a) == set(b)
            for k in a:
                np.testing.assert_array_equal(a[k], b[k])

    @pytest.mark.parametrize("cache_mode", ["always", "never"])
    def test_epoch_replay_detects_truncated_file(self, mesh, tmp_path, rng,
                                                 cache_mode):
        # VERDICT r3 #7: steady-state epochs trust the epoch-1 round
        # count; a file truncated between epochs must raise loudly, not
        # silently desynchronize the collective batch contract.
        # Truncation lands on a line boundary so every remaining byte
        # parses cleanly — only the replay-length check can catch it.
        from dmlc_tpu.utils.logging import DMLCError
        p = tmp_path / "d.libsvm"
        self._write_libsvm(p, rng, 300)
        it = ShardedRowBlockIter(str(p), mesh, format="libsvm",
                                 row_bucket=8, nnz_bucket=16,
                                 prefetch=False,
                                 first_epoch_cache=cache_mode)
        n1 = len(self._collect(it))
        assert n1 > 0
        data = p.read_bytes()
        cut = data.index(b"\n", len(data) // 4) + 1
        p.write_bytes(data[:cut])  # clean truncation at a line boundary
        with pytest.raises(DMLCError, match="changed between epochs"):
            self._collect(it)

    def test_epoch_replay_detects_rewritten_file(self, mesh, tmp_path, rng):
        # a rewrite with different bytes typically breaks mid-token at
        # the old shard boundaries; the replay wraps the parse error
        # with the file-mutation context
        from dmlc_tpu.utils.logging import DMLCError
        p = tmp_path / "d.libsvm"
        self._write_libsvm(p, rng, 300)
        it = ShardedRowBlockIter(str(p), mesh, format="libsvm",
                                 row_bucket=8, nnz_bucket=16,
                                 prefetch=False)
        assert len(self._collect(it)) > 0
        self._write_libsvm(p, rng, 40)  # rewrite, much shorter
        with pytest.raises(DMLCError, match="changed between epochs"):
            self._collect(it)

    def test_epoch_replay_ignores_appended_data(self, mesh, tmp_path, rng):
        # shard byte-ranges are captured at creation, so data APPENDED
        # after the iterator was built is invisible: replay stays loyal
        # to epoch 1 (documented behavior, not a hazard)
        p = tmp_path / "d.libsvm"
        self._write_libsvm(p, rng, 150)
        it = ShardedRowBlockIter(str(p), mesh, format="libsvm",
                                 row_bucket=32, nnz_bucket=64,
                                 prefetch=False)
        e1 = self._collect(it)
        with open(p, "ab") as f:
            f.write(b"1 3:0.5\n" * 200)
        e2 = self._collect(it)
        assert len(e1) == len(e2)
        for a, b in zip(e1, e2):
            for k in a:
                np.testing.assert_array_equal(a[k], b[k])

    def test_steady_replay_serves_from_memory(self, mesh, tmp_path, rng):
        # VERDICT r4 #2: with the epoch-1 cache on, steady epochs must
        # REPLAY the retained rounds (no re-parse) and still match
        # epoch 1 exactly
        p = tmp_path / "d.libsvm"
        self._write_libsvm(p, rng, 150)
        it = ShardedRowBlockIter(str(p), mesh, format="libsvm",
                                 row_bucket=32, nnz_bucket=64,
                                 prefetch=False,
                                 first_epoch_cache="always")
        e1 = self._collect(it)
        assert it.replay_epochs == 0
        e2 = self._collect(it)
        assert it.replay_epochs == 1  # epoch 2 came from memory
        e3 = self._collect(it)
        assert it.replay_epochs == 2
        for a, b in zip(e1, e2):
            for k in a:
                np.testing.assert_array_equal(a[k], b[k])
        for a, b in zip(e1, e3):
            for k in a:
                np.testing.assert_array_equal(a[k], b[k])

    def test_single_process_auto_tees_then_replays(self, mesh, tmp_path,
                                                   rng):
        # single-process "auto" streams epoch 1 (no cache), re-parses +
        # tees epoch 2, replays epoch 3+ — all identical
        p = tmp_path / "d.libsvm"
        self._write_libsvm(p, rng, 150)
        it = ShardedRowBlockIter(str(p), mesh, format="libsvm",
                                 row_bucket=32, nnz_bucket=64,
                                 prefetch=False)
        e1 = self._collect(it)
        e2 = self._collect(it)
        assert it.replay_epochs == 0  # epoch 2 re-parsed (the tee)
        e3 = self._collect(it)
        assert it.replay_epochs == 1  # epoch 3 replayed the tee
        for a, b in zip(e1, e3):
            for k in a:
                np.testing.assert_array_equal(a[k], b[k])
        assert len(e1) == len(e2) == len(e3)

    def test_append_after_replay_reparses_then_reearns(self, mesh,
                                                       tmp_path, rng):
        # appended bytes are invisible (byte-ranges captured at
        # creation): a replay-armed iterator must notice the stat
        # change, fall back to one clean re-parse epoch, and re-earn
        # replay — never serve an error, never serve the appended rows
        p = tmp_path / "d.libsvm"
        self._write_libsvm(p, rng, 150)
        it = ShardedRowBlockIter(str(p), mesh, format="libsvm",
                                 row_bucket=32, nnz_bucket=64,
                                 prefetch=False,
                                 first_epoch_cache="always")
        e1 = self._collect(it)
        with open(p, "ab") as f:
            f.write(b"1 3:0.5\n" * 200)
        e2 = self._collect(it)
        assert it.replay_epochs == 0  # stat changed: epoch 2 re-parsed
        e3 = self._collect(it)
        assert it.replay_epochs == 1  # stable again: epoch 3 replayed
        for a, b in zip(e1, e2):
            for k in a:
                np.testing.assert_array_equal(a[k], b[k])
        for a, b in zip(e1, e3):
            for k in a:
                np.testing.assert_array_equal(a[k], b[k])

    @staticmethod
    def _epoch_hash(batches):
        """Content hash of one epoch's batch stream (order- and
        key-sensitive) — the byte-parity probe for replay tiers."""
        import hashlib
        h = hashlib.sha256()
        for gb in batches:
            for k in sorted(gb):
                h.update(k.encode())
                h.update(np.ascontiguousarray(gb[k]).tobytes())
        return h.hexdigest()

    def test_page_spill_serves_steady_epochs_byte_identical(
            self, mesh, tmp_path, rng):
        # ISSUE 2 tentpole: an 8-device gang whose rounds exceed a
        # deliberately tiny agreement_cache_bytes must SPILL the rounds
        # to the binary page cache instead of abandoning replay, and
        # every steady epoch must serve from pages with batches
        # content-hash-identical to epoch 1
        p = tmp_path / "d.libsvm"
        self._write_libsvm(p, rng, 300)
        it = ShardedRowBlockIter(str(p), mesh, format="libsvm",
                                 row_bucket=32, nnz_bucket=64,
                                 prefetch=False,
                                 agreement_cache_bytes=2048,  # << shard
                                 spill_dir=str(tmp_path / "spill"),
                                 first_epoch_cache="always")
        e1 = self._collect(it)
        assert it.replay_tier == "parse"
        assert it._round_store is not None
        assert it._round_store.tier == "pages"
        spill_path = it._round_store.file.path
        assert os.path.exists(spill_path)
        e2 = self._collect(it)
        assert it.replay_tier == "pages"
        assert (it.replay_epochs, it.page_replay_epochs) == (1, 1)
        e3 = self._collect(it)
        assert (it.replay_epochs, it.page_replay_epochs) == (2, 2)
        assert (self._epoch_hash(e1) == self._epoch_hash(e2)
                == self._epoch_hash(e3))
        it.close()
        assert not os.path.exists(spill_path), \
            "close() must delete the spill file"

    def test_page_spill_tees_on_second_epoch(self, mesh, tmp_path, rng):
        # no epoch-1 cache ("never"): epoch 1 parses, epoch 2 re-parses
        # and tees its over-budget rounds to pages, and every epoch
        # after that replays the pages — all byte-identical
        p = tmp_path / "d.libsvm"
        self._write_libsvm(p, rng, 300)
        it = ShardedRowBlockIter(str(p), mesh, format="libsvm",
                                 row_bucket=32, nnz_bucket=64,
                                 prefetch=False,
                                 agreement_cache_bytes=2048,  # << shard
                                 spill_dir=str(tmp_path / "spill"),
                                 first_epoch_cache="never")
        e1 = self._collect(it)
        e2 = self._collect(it)
        assert it.replay_tier == "parse"
        assert it._round_store is not None
        assert it._round_store.tier == "pages"
        replays = [self._collect(it) for _ in range(3)]
        assert it.replay_tier == "pages"
        assert (it.replay_epochs, it.page_replay_epochs) == (3, 3)
        want = self._epoch_hash(e1)
        assert self._epoch_hash(e2) == want
        assert all(self._epoch_hash(e) == want for e in replays)
        it.close()

    @pytest.mark.parametrize("protocol", ["one_vote", "peer_over_budget",
                                          "per_round"])
    def test_gang_round_agreement_pads_short_rank(self, mesh, tmp_path,
                                                  rng, monkeypatch,
                                                  protocol):
        # a two-process gang seen from the rank with FEWER rounds: the
        # peer's answers come from a stand-in allgather. Whichever
        # epoch-1 protocol the gang agrees on (one vote, or the
        # per-round done flags when any rank is over its cache
        # budget), this rank yields the gang's round count, padding
        # with empty batches, and its steady epoch matches epoch 1.
        from jax.experimental import multihost_utils

        import dmlc_tpu.parallel.sharded as sharded_mod
        p = tmp_path / "d.libsvm"
        self._write_libsvm(p, rng, 200)
        kw = dict(format="libsvm", row_bucket=32, nnz_bucket=64,
                  prefetch=False, first_epoch_cache="always")
        if protocol == "per_round":
            kw["agreement_cache_bytes"] = 0
        alone = self._collect(ShardedRowBlockIter(str(p), mesh, **kw))
        peer_rounds = len(alone) + 3
        calls = {"vote": 0, "round": 0}

        def allgather(x):
            x = np.asarray(x)
            if x.dtype == np.int64:  # the one-vote epoch-1 agreement
                calls["vote"] += 1
                cached = 0 if protocol == "peer_over_budget" else 1
                peer = np.array([cached, peer_rounds, 0, 0], np.int64)
            else:                    # a per-round done flag
                peer = np.array([calls["round"] >= peer_rounds,
                                 False, False])
                calls["round"] += 1
            return np.stack([x, peer])

        monkeypatch.setattr(multihost_utils, "process_allgather",
                            allgather)

        class TwoProcessJax:
            def __getattr__(self, name):
                return getattr(jax, name)

            @staticmethod
            def process_count():
                return 2

        monkeypatch.setattr(sharded_mod, "jax", TwoProcessJax())
        it = ShardedRowBlockIter(str(p), mesh, **kw)
        e1 = self._collect(it)
        e2 = self._collect(it)
        assert calls["vote"] == 1
        assert calls["round"] == (0 if protocol == "one_vote"
                                  else peer_rounds + 1)
        assert len(e1) == len(e2) == peer_rounds
        assert self._epoch_hash(e1[:len(alone)]) == \
            self._epoch_hash(alone)
        assert all(int(b["num_rows"].sum()) == 0
                   for b in e1[len(alone):])
        assert self._epoch_hash(e2) == self._epoch_hash(e1)
        it.close()

    def test_page_spill_mutation_reparses_then_reearns(self, mesh,
                                                       tmp_path, rng):
        # the mutation contract is tier-independent: a page-armed
        # iterator must notice the stat change, fall back to one clean
        # asserting re-parse epoch (appends stay invisible), and
        # re-earn PAGE replay — never serve stale pages
        p = tmp_path / "d.libsvm"
        self._write_libsvm(p, rng, 300)
        it = ShardedRowBlockIter(str(p), mesh, format="libsvm",
                                 row_bucket=32, nnz_bucket=64,
                                 prefetch=False,
                                 agreement_cache_bytes=2048,
                                 spill_dir=str(tmp_path / "spill"),
                                 first_epoch_cache="always")
        e1 = self._collect(it)
        first_spill = it._round_store.file.path
        with open(p, "ab") as f:
            f.write(b"1 3:0.5\n" * 200)
        e2 = self._collect(it)
        assert it.replay_tier == "parse"      # stat change: re-parse
        assert it.page_replay_epochs == 0
        assert not os.path.exists(first_spill), \
            "stale spill file must be dropped with its store"
        e3 = self._collect(it)
        assert it.replay_tier == "pages"      # stable again: re-earned
        assert it.page_replay_epochs == 1
        assert (self._epoch_hash(e1) == self._epoch_hash(e2)
                == self._epoch_hash(e3))
        it.close()

    def test_page_spill_truncation_still_raises(self, mesh, tmp_path,
                                                rng):
        # page tier must not weaken the hazard detection: truncating
        # the backing file under a page-armed iterator raises the
        # mutation error on the fallback re-parse, same as r5
        from dmlc_tpu.utils.logging import DMLCError
        p = tmp_path / "d.libsvm"
        self._write_libsvm(p, rng, 300)
        it = ShardedRowBlockIter(str(p), mesh, format="libsvm",
                                 row_bucket=8, nnz_bucket=16,
                                 prefetch=False,
                                 agreement_cache_bytes=2048,
                                 spill_dir=str(tmp_path / "spill"),
                                 first_epoch_cache="always")
        assert len(self._collect(it)) > 0
        assert it._round_store is not None
        data = p.read_bytes()
        cut = data.index(b"\n", len(data) // 4) + 1
        p.write_bytes(data[:cut])
        with pytest.raises(DMLCError, match="changed between epochs"):
            self._collect(it)

    def test_raw_rounds_beat_padded_on_short_rows(self, mesh, tmp_path,
                                                  rng):
        # the RSS model's multiplier: on a short-row corpus the raw
        # retained rounds must sit WELL below the padded bytes the r5
        # tee held (nnz_bucket sized for the worst row, short rows
        # leave most of it as pad) — the reason the r6 tee retains raw
        p = tmp_path / "short.libsvm"
        self._write_libsvm(p, rng, 400)  # 1 feature per row
        it = ShardedRowBlockIter(str(p), mesh, format="libsvm",
                                 row_bucket=64, nnz_bucket=1 << 10,
                                 prefetch=False,
                                 first_epoch_cache="always")
        e1 = self._collect(it)
        store = it._round_store
        assert store is not None and store.tier == "memory"
        padded_bytes = sum(int(v.nbytes) for gb in e1
                           for v in gb.values())
        assert store.nbytes < padded_bytes / 4, (
            store.nbytes, padded_bytes)

    def test_page_spill_off_abandons_over_budget(self, mesh, tmp_path,
                                                 rng):
        # page_spill=False restores the pre-r6 behavior: over-budget
        # rounds abandon replay and every epoch re-parses (identically)
        p = tmp_path / "d.libsvm"
        self._write_libsvm(p, rng, 200)
        it = ShardedRowBlockIter(str(p), mesh, format="libsvm",
                                 row_bucket=32, nnz_bucket=64,
                                 prefetch=False,
                                 agreement_cache_bytes=2048,
                                 page_spill=False,
                                 first_epoch_cache="always")
        e1 = self._collect(it)
        e2 = self._collect(it)
        assert it.replay_epochs == 0
        assert it._round_store is None
        assert self._epoch_hash(e1) == self._epoch_hash(e2)

    def test_steady_replay_off_reparses_every_epoch(self, mesh, tmp_path,
                                                    rng):
        p = tmp_path / "d.libsvm"
        self._write_libsvm(p, rng, 100)
        it = ShardedRowBlockIter(str(p), mesh, format="libsvm",
                                 row_bucket=32, nnz_bucket=64,
                                 prefetch=False, steady_replay=False,
                                 first_epoch_cache="always")
        e1 = self._collect(it)
        e2 = self._collect(it)
        assert it.replay_epochs == 0
        for a, b in zip(e1, e2):
            for k in a:
                np.testing.assert_array_equal(a[k], b[k])

    @pytest.mark.parametrize("cache_mode", ["always", "never"])
    def test_skewed_qid_parts_pad_consistent_schema(self, mesh, tmp_path,
                                                    rng, cache_mode):
        # ADVICE r4 (medium): on a qid-bearing source, a part that
        # exhausts before the global round count pads with empty blocks
        # — those pads must carry the SAME key set (qid = -1) or
        # stack_device_batches raises 'inconsistent batch keys'. Row
        # lengths vary wildly so equal BYTE shards hold very different
        # row counts: early parts replay many more rounds than late
        # ones (verified: part_rounds like [16, 11, 3, ...]).
        lines = []
        for i in range(200):
            lines.append(f"{i % 2} qid:{i // 3} {rng.randint(0, 9)}:1")
        for i in range(20):
            feats = " ".join(
                f"{j}:{rng.rand():.6f}"
                for j in sorted(rng.choice(500, 40, replace=False)))
            lines.append(f"{i % 2} qid:{100 + i} {feats}")
        p = tmp_path / "rank.libsvm"
        p.write_bytes("\n".join(lines).encode() + b"\n")
        it = ShardedRowBlockIter(str(p), mesh, format="libsvm",
                                 row_bucket=8, nnz_bucket=64,
                                 prefetch=False,
                                 first_epoch_cache=cache_mode)
        for epoch in range(2):
            batches = self._collect(it)
            assert len(batches) > 0
            for gb in batches:
                assert "qid" in gb  # every batch carries the schema
                q = gb["qid"]
                n = gb["num_rows"]
                for d in range(q.shape[0]):
                    assert (q[d, int(n[d]):] == -1).all()  # neutral pad
        assert len(set(it._part_rounds)) > 1  # the skew actually happened

    def test_skewed_field_parts_pad_consistent_schema(self, mesh,
                                                      tmp_path, rng):
        # same hazard for the libfm field[] column (field pads 0):
        # short rows first, long rows last, so byte shards skew
        lines = []
        for i in range(200):
            lines.append(f"{i % 2} 1:{rng.randint(0, 9)}:1")
        for i in range(20):
            toks = " ".join(
                f"{rng.randint(0, 6)}:{j}:{rng.rand():.6f}"
                for j in sorted(rng.choice(500, 40, replace=False)))
            lines.append(f"{i % 2} {toks}")
        p = tmp_path / "f.libfm"
        p.write_bytes("\n".join(lines).encode() + b"\n")
        it = ShardedRowBlockIter(str(p), mesh, format="libfm",
                                 row_bucket=8, nnz_bucket=64,
                                 prefetch=False)
        for epoch in range(2):
            batches = self._collect(it)
            assert len(batches) > 0
            for gb in batches:
                assert "field" in gb
        assert len(set(it._part_rounds)) > 1  # the skew actually happened

    def test_second_epoch_matches_first(self, mesh, tmp_path, rng):
        # the steady-state replay (no collectives, counted rounds) must
        # reproduce epoch 1's batches exactly
        p = tmp_path / "d.libsvm"
        self._write_libsvm(p, rng, 150)
        it = ShardedRowBlockIter(str(p), mesh, format="libsvm",
                                 row_bucket=32, nnz_bucket=64,
                                 prefetch=False,
                                 first_epoch_cache="always")
        e1 = self._collect(it)
        e2 = self._collect(it)
        assert len(e1) == len(e2)
        for a, b in zip(e1, e2):
            for k in a:
                np.testing.assert_array_equal(a[k], b[k])


class TestSparseRankingModel:
    """Pairwise RankNet loss — the consumer of the libsvm qid column:
    loss must match a brute-force pairwise golden, training must raise
    pairwise accuracy on a planted scorer, and sharded == flat when qid
    groups stay within device blocks."""

    @staticmethod
    def _ranking_block(rng, nqueries, ncol, docs_per_q=6):
        c = RowBlockContainer(np.uint32)
        w_true = np.random.RandomState(5).randn(ncol).astype(np.float32)
        for q in range(nqueries):
            for _ in range(docs_per_q):
                nnz = rng.randint(2, 6)
                idx = np.sort(rng.choice(ncol, nnz, replace=False))
                val = rng.rand(nnz).astype(np.float32)
                score = float((val * w_true[idx]).sum())
                # graded relevance from the hidden scorer (0/1/2)
                c.push(float(np.digitize(score, [0.5, 1.2])), idx, val,
                       qid=q)
        return c.get_block()

    @staticmethod
    def _brute_force_loss(params, batch):
        """The objective verbatim: softplus(-(m_i - m_j)) over same-qid
        pairs with label_i > label_j, weight-weighted mean."""
        from dmlc_tpu.models import SparseRankingModel
        w = np.asarray(params["w"]).astype(np.float64)
        b = float(params["b"])
        off = np.asarray(batch["offset"])
        idx = np.asarray(batch["index"]).astype(int)
        val = np.asarray(batch["value"]).astype(np.float64)
        lab = np.asarray(batch["label"])
        qid = np.asarray(batch["qid"])
        wt = np.asarray(batch["weight"]).astype(np.float64)
        n = lab.shape[0]
        m = np.array([b + (val[off[i]:off[i + 1]]
                           * w[idx[off[i]:off[i + 1]]]).sum()
                      for i in range(n)])
        num = den = 0.0
        for i in range(n):
            for j in range(n):
                if qid[i] >= 0 and qid[i] == qid[j] and lab[i] > lab[j]:
                    pw = wt[i] * wt[j]
                    num += pw * np.log1p(np.exp(-(m[i] - m[j])))
                    den += pw
        # true weighted mean (the production max(den, 1) clamp was
        # removed in r4; den == 0 means no pairs)
        return num / den if den > 0 else 0.0

    def test_loss_matches_brute_force(self, rng):
        from dmlc_tpu.models import SparseRankingModel
        block = self._ranking_block(rng, nqueries=5, ncol=20)
        batch = pad_to_bucket(block, 64, 512)
        model = SparseRankingModel(20)
        params = {"w": np.asarray(rng.randn(20), np.float32),
                  "b": np.float32(0.1)}
        got = float(model.loss(params, batch))
        want = self._brute_force_loss(params, batch)
        assert got == pytest.approx(want, rel=1e-5)

    def test_training_improves_pairwise_accuracy(self, rng):
        from dmlc_tpu.models import SparseRankingModel
        block = self._ranking_block(rng, nqueries=24, ncol=24)
        batch = pad_to_bucket(block, 256, 2048)
        model = SparseRankingModel(24, learning_rate=1.0)
        params = model.init_params()
        acc0 = model.pairwise_accuracy(params, batch)
        for _ in range(60):
            params, loss = model.train_step(params, batch)
        acc1 = model.pairwise_accuracy(params, batch)
        assert np.isfinite(float(loss))
        assert acc1 > max(acc0, 0.8), (acc0, acc1)

    def test_sharded_step_matches_single_chip(self, mesh, rng):
        from dmlc_tpu.models import SparseRankingModel
        ncol = 18
        # one block per device, DISTINCT qids per device: no group
        # straddles a shard, so within-block pairs == all pairs and
        # sharded must equal flat exactly
        blocks = []
        for d in range(8):
            c = RowBlockContainer(np.uint32)
            w_true = np.random.RandomState(5).randn(ncol)
            for q in range(2):
                for _ in range(4):
                    nnz = rng.randint(2, 5)
                    idx = np.sort(rng.choice(ncol, nnz, replace=False))
                    val = rng.rand(nnz).astype(np.float32)
                    s = float((val * w_true[idx]).sum())
                    c.push(float(s > 0.8), idx, val, qid=d * 2 + q)
            blocks.append(c.get_block())
        locals_ = [pad_to_bucket(b, 8, 64) for b in blocks]
        gb = make_global_batch(stack_device_batches(locals_), mesh)
        model = SparseRankingModel(ncol, learning_rate=0.2)
        params = model.init_params()
        p1, loss_sharded = model.make_sharded_train_step(mesh)(params, gb)

        c = RowBlockContainer(np.uint32)
        for b in blocks:
            c.push_block(b)
        flat = pad_to_bucket(c.get_block(), 64, 512)
        p2, loss_flat = model.train_step(params, flat)
        assert float(loss_sharded) == pytest.approx(float(loss_flat),
                                                    rel=1e-5)
        np.testing.assert_allclose(np.asarray(p1["w"]), np.asarray(p2["w"]),
                                   rtol=1e-4, atol=1e-6)

    def test_missing_qid_raises_named_error(self, mesh, rng):
        # a qid-less batch must fail with the real cause, not a bare
        # KeyError inside a jit trace — on BOTH the flat and the
        # sharded path
        from dmlc_tpu.models import SparseRankingModel
        from dmlc_tpu.utils.logging import DMLCError
        block = random_block(rng, rows=8)
        batch = pad_to_bucket(block, 8, 64)  # no qid column
        model = SparseRankingModel(50)
        with pytest.raises(DMLCError, match="qid"):
            model.loss(model.init_params(), batch)
        locals_ = [pad_to_bucket(random_block(rng, rows=4), 8, 64)
                   for _ in range(8)]
        gb = make_global_batch(stack_device_batches(locals_), mesh)
        with pytest.raises(DMLCError, match="qid"):
            model.make_sharded_train_step(mesh)(model.init_params(), gb)

    def test_sub_unit_weights_use_true_weighted_mean(self, rng):
        # pair weights are PRODUCTS of instance weights: with weights
        # 0.1 the total pair weight is << 1, and the old max(wsum, 1)
        # clamp would silently shrink the loss; the weighted mean must
        # be invariant to a uniform instance-weight rescale
        from dmlc_tpu.models import SparseRankingModel
        block = self._ranking_block(rng, nqueries=4, ncol=16)
        b1 = pad_to_bucket(block, 32, 256)
        b2 = {k: (v.copy() if hasattr(v, "copy") else v)
              for k, v in b1.items()}
        b2["weight"] = b2["weight"] * 0.1
        model = SparseRankingModel(16)
        params = {"w": np.asarray(rng.randn(16), np.float32),
                  "b": np.float32(0.0)}
        l1 = float(model.loss(params, b1))
        l2 = float(model.loss(params, b2))
        assert l1 == pytest.approx(l2, rel=1e-5), (l1, l2)

    def test_oversized_row_bucket_raises_at_trace(self, rng):
        # the pairwise loss is O(n^2) memory: an oversized batch must
        # fail loudly at trace time, not OOM on device
        from dmlc_tpu.models import SparseRankingModel
        from dmlc_tpu.utils.logging import DMLCError
        block = self._ranking_block(rng, nqueries=3, ncol=12)
        batch = pad_to_bucket(block, 64, 512)
        model = SparseRankingModel(12, max_row_bucket=32)
        with pytest.raises(DMLCError, match="max_row_bucket"):
            model.loss(model.init_params(), batch)

    def test_libsvm_qid_to_training(self, tmp_path, rng):
        """End-to-end: libsvm text WITH qid tokens → Parser → padded
        batch → ranking step — qid flows to the device and is
        consumed."""
        from dmlc_tpu.models import SparseRankingModel
        ncol = 16
        lines = []
        for q in range(30):
            for _ in range(5):
                nnz = rng.randint(2, 6)
                idx = np.sort(rng.choice(ncol, nnz, replace=False))
                feats = " ".join(f"{j}:{rng.rand():.4f}" for j in idx)
                lines.append(f"{rng.randint(0, 3)} qid:{q} {feats}")
        p = tmp_path / "rank.libsvm"
        p.write_text("\n".join(lines) + "\n")
        c = RowBlockContainer(np.uint32)
        parser = Parser.create(str(p), 0, 1, format="libsvm")
        for b in parser:
            c.push_block(b)
        if hasattr(parser, "destroy"):
            parser.destroy()
        block = c.get_block()
        assert block.qid is not None
        batch = pad_to_bucket(block, next_pow2_bucket(block.size),
                              next_pow2_bucket(block.nnz))
        assert "qid" in batch
        model = SparseRankingModel(ncol, learning_rate=0.5)
        params = model.init_params()
        losses = []
        for _ in range(20):
            params, loss = model.train_step(params, batch)
            losses.append(float(loss))
        assert np.isfinite(losses[-1]) and losses[-1] <= losses[0]


class TestDevicePrefetch:
    def test_preserves_order_and_values(self, rng):
        batches = [{"x": rng.rand(4).astype(np.float32)} for _ in range(7)]
        out = list(device_prefetch(iter(batches), size=3))
        assert len(out) == 7
        for a, b in zip(batches, out):
            np.testing.assert_array_equal(a["x"], np.asarray(b["x"]))
            assert isinstance(b["x"], jax.Array)

    def test_device_iter_protocol(self, rng):
        batches = [{"x": np.full(2, i, np.float32)} for i in range(4)]
        it = DeviceIter(lambda: iter(batches), size=2)
        got = [float(np.asarray(b["x"])[0]) for b in it]
        assert got == [0.0, 1.0, 2.0, 3.0]
        got2 = [float(np.asarray(b["x"])[0]) for b in it]  # replay
        assert got2 == got


class TestSparseLinearModel:
    def test_single_chip_training_decreases_loss(self, rng):
        ncol = 32
        c = RowBlockContainer(np.uint32)
        w_true = rng.randn(ncol).astype(np.float32)
        for _ in range(256):
            nnz = rng.randint(1, 8)
            idx = np.sort(rng.choice(ncol, nnz, replace=False))
            val = rng.rand(nnz).astype(np.float32)
            margin = (val * w_true[idx]).sum()
            c.push(1.0 if margin > 0 else -1.0, idx, val)
        block = c.get_block()
        batch = pad_to_bucket(block, 256, 2048)
        model = SparseLinearModel(ncol, learning_rate=0.5)
        params = model.init_params()
        losses = []
        for _ in range(20):
            params, loss = model.train_step(params, batch)
            losses.append(float(loss))
        assert losses[-1] < losses[0] * 0.9

    def test_sharded_step_matches_single_chip(self, mesh, rng):
        ncol = 24
        blocks = [random_block(rng, rows=8, ncol=ncol) for _ in range(8)]
        locals_ = [pad_to_bucket(b, 8, 64) for b in blocks]
        gb = make_global_batch(stack_device_batches(locals_), mesh)
        model = SparseLinearModel(ncol, learning_rate=0.1)
        params = model.init_params()
        sharded_step = model.make_sharded_train_step(mesh)
        p1, loss_sharded = sharded_step(params, gb)

        # single-chip equivalent: all 64 rows in one flat batch
        c = RowBlockContainer(np.uint32)
        for b in blocks:
            c.push_block(b)
        flat = pad_to_bucket(c.get_block(), 64, 512)
        p2, loss_flat = model.train_step(params, flat)
        assert float(loss_sharded) == pytest.approx(float(loss_flat),
                                                    rel=1e-5)
        np.testing.assert_allclose(np.asarray(p1["w"]), np.asarray(p2["w"]),
                                   rtol=1e-4, atol=1e-6)


class TestSparseFMModel:
    """Second-order FM (the libfm-family consumer): must fit pure
    feature INTERACTIONS a linear model provably cannot, and its sharded
    step must match the flat single-chip step."""

    @staticmethod
    def _xor_blocks(rng, rows, npairs=4):
        """Label = XOR of which feature of a pair fires — zero linear
        signal, pure pairwise signal."""
        c = RowBlockContainer(np.uint32)
        for _ in range(rows):
            a = rng.randint(npairs)          # pair id
            b = rng.randint(2)               # which side of the pair
            cbit = rng.randint(2)
            # features: 2 per pair + 2 "context" features
            idx = np.array(sorted({2 * a + b, 2 * npairs + cbit}), np.uint32)
            label = 1.0 if b == cbit else -1.0   # interaction-only rule
            c.push(label, idx, np.ones(len(idx), np.float32))
        return c.get_block()

    def test_fm_learns_interactions_linear_cannot(self, rng):
        ncol = 10
        block = self._xor_blocks(rng, rows=512)
        batch = pad_to_bucket(block, 512, 2048)
        fm = SparseFMModel(ncol, num_factors=4, learning_rate=0.5)
        lin = SparseLinearModel(ncol, learning_rate=0.5)
        fparams, lparams = fm.init_params(seed=3), lin.init_params()
        flosses, llosses = [], []
        for _ in range(150):
            fparams, fl = fm.train_step(fparams, batch)
            flosses.append(float(fl))
            lparams, ll = lin.train_step(lparams, batch)
            llosses.append(float(ll))
        assert flosses[-1] < 0.45, flosses[-1]           # FM fits XOR
        assert llosses[-1] > 0.6, llosses[-1]            # linear cannot
        # and prediction accuracy beats chance decisively
        proba = np.asarray(fm.predict_proba(fparams, batch))
        y = np.asarray(batch["label"]) > 0
        acc = ((proba > 0.5) == y)[: block.size].mean()
        assert acc > 0.9, acc

    def test_sharded_step_matches_single_chip(self, mesh, rng):
        ncol = 24
        blocks = [random_block(rng, rows=8, ncol=ncol) for _ in range(8)]
        locals_ = [pad_to_bucket(b, 8, 64) for b in blocks]
        gb = make_global_batch(stack_device_batches(locals_), mesh)
        model = SparseFMModel(ncol, num_factors=4, learning_rate=0.1)
        params = model.init_params(seed=1)
        sharded_step = model.make_sharded_train_step(mesh)
        p1, loss_sharded = sharded_step(params, gb)

        c = RowBlockContainer(np.uint32)
        for b in blocks:
            c.push_block(b)
        flat = pad_to_bucket(c.get_block(), 64, 512)
        p2, loss_flat = model.train_step(params, flat)
        assert float(loss_sharded) == pytest.approx(float(loss_flat),
                                                    rel=1e-5)
        np.testing.assert_allclose(np.asarray(p1["w"]), np.asarray(p2["w"]),
                                   rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(np.asarray(p1["V"]), np.asarray(p2["V"]),
                                   rtol=1e-4, atol=1e-6)

    def test_libfm_file_to_training(self, tmp_path, rng):
        """End-to-end: libfm text → Parser → padded batch → FM step (the
        format family's canonical consumer loop)."""
        ncol = 16
        lines = []
        for i in range(200):
            nnz = rng.randint(1, 6)
            idx = np.sort(rng.choice(ncol, nnz, replace=False))
            toks = " ".join(
                f"{rng.randint(0, 4)}:{j}:{rng.rand():.4f}" for j in idx)
            lines.append(f"{i % 2} {toks}")
        p = tmp_path / "d.libfm"
        p.write_text("\n".join(lines) + "\n")
        c = RowBlockContainer(np.uint32)
        parser = Parser.create(str(p), 0, 1, format="libfm")
        for b in parser:
            c.push_block(b)
        if hasattr(parser, "destroy"):
            parser.destroy()
        block = c.get_block()
        assert block.field is not None  # libfm parsed fields
        batch = pad_to_bucket(block, next_pow2_bucket(block.size),
                              next_pow2_bucket(block.nnz))
        model = SparseFMModel(ncol, num_factors=2, learning_rate=0.2)
        params = model.init_params()
        _, l0 = model.train_step(params, batch)
        assert np.isfinite(float(l0))


class TestSparseFFMModel:
    """Field-aware FM — the consumer of the libfm field[] column
    (VERDICT r3 #8): forward must match the brute-force pairwise FFM
    definition (which proves the field pairing is real, not FM in
    disguise), and the sharded step must match the flat step."""

    @staticmethod
    def _ffm_batch(rng, rows, ncol, nfields, row_bucket, nnz_bucket):
        c = RowBlockContainer(np.uint32)
        fields = rng.randint(0, nfields, size=ncol)  # feature -> field
        for _ in range(rows):
            nnz = rng.randint(1, 6)
            idx = np.sort(rng.choice(ncol, nnz, replace=False))
            c.push(float(rng.randint(0, 2) * 2 - 1), idx,
                   rng.rand(nnz).astype(np.float32),
                   fields=fields[idx].astype(np.int64))
        block = c.get_block()
        assert block.field is not None
        return pad_to_bucket(block, row_bucket, nnz_bucket), block

    @staticmethod
    def _brute_force_margins(params, block):
        """The FFM definition verbatim: b + Σ w_i x_i +
        Σ_{i<j} <v_{i,f_j}, v_{j,f_i}> x_i x_j, row by row."""
        w = np.asarray(params["w"])
        V = np.asarray(params["V"])
        bias = float(params["b"])
        out = []
        for r in range(block.size):
            s, e = int(block.offset[r]), int(block.offset[r + 1])
            idx = block.index[s:e].astype(int)
            val = block.value[s:e].astype(np.float64)
            fld = block.field[s:e].astype(int)
            m = bias + float((w[idx] * val).sum())
            for a in range(len(idx)):
                for b2 in range(a + 1, len(idx)):
                    m += float(np.dot(V[idx[a], fld[b2]],
                                      V[idx[b2], fld[a]])
                               * val[a] * val[b2])
            out.append(m)
        return np.array(out, np.float64)

    def test_forward_matches_brute_force(self, rng):
        from dmlc_tpu.models import SparseFFMModel
        ncol, nfields = 20, 3
        batch, block = self._ffm_batch(rng, 64, ncol, nfields, 64, 512)
        model = SparseFFMModel(ncol, nfields, num_factors=4)
        params = model.init_params(seed=1)
        got = np.asarray(model.forward(params, batch))[: block.size]
        want = self._brute_force_margins(params, block)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)

    def test_training_fits_planted_ffm_signal(self, rng):
        # labels come from a TEACHER FFM (brute-force margins of random
        # teacher params): a learnable field-aware signal, so training
        # must fit it well — random labels would only allow memorization
        from dmlc_tpu.models import SparseFFMModel
        ncol, nfields = 16, 4
        batch, block = self._ffm_batch(rng, 256, ncol, nfields, 256, 2048)
        teacher = SparseFFMModel(ncol, nfields, num_factors=4,
                                 init_scale=1.0)
        margins = self._brute_force_margins(teacher.init_params(seed=9),
                                            block)
        batch["label"][: block.size] = np.where(margins > np.median(
            margins), 1.0, -1.0).astype(np.float32)
        model = SparseFFMModel(ncol, nfields, num_factors=4,
                               learning_rate=2.0, init_scale=0.1)
        params = model.init_params(seed=2)
        losses = []
        for _ in range(200):
            params, loss = model.train_step(params, batch)
            losses.append(float(loss))
        assert losses[-1] < losses[0] * 0.72, losses[::40]

    def test_sharded_step_matches_single_chip(self, mesh, rng):
        from dmlc_tpu.models import SparseFFMModel
        ncol, nfields = 18, 3
        per_dev = [self._ffm_batch(rng, 8, ncol, nfields, 8, 64)
                   for _ in range(8)]
        locals_ = [b for b, _ in per_dev]
        gb = make_global_batch(stack_device_batches(locals_), mesh)
        model = SparseFFMModel(ncol, nfields, num_factors=2,
                               learning_rate=0.1)
        params = model.init_params(seed=4)
        p1, loss_sharded = model.make_sharded_train_step(mesh)(params, gb)

        c = RowBlockContainer(np.uint32)
        for _, blk in per_dev:
            c.push_block(blk)
        flat = pad_to_bucket(c.get_block(), 64, 512)
        p2, loss_flat = model.train_step(params, flat)
        assert float(loss_sharded) == pytest.approx(float(loss_flat),
                                                    rel=1e-5)
        np.testing.assert_allclose(np.asarray(p1["V"]), np.asarray(p2["V"]),
                                   rtol=1e-4, atol=1e-6)

    def test_validate_batch_rejects_out_of_range_fields(self, rng):
        # the jitted forward CLIPS out-of-range field ids (XLA gather
        # must be in-bounds) — the host-side validator is what turns a
        # num_fields misconfiguration into an error instead of silent
        # field merging
        from dmlc_tpu.models import SparseFFMModel
        from dmlc_tpu.utils.logging import DMLCError
        batch, _ = self._ffm_batch(rng, 16, 12, 5, 16, 128)
        model = SparseFFMModel(12, num_fields=2, num_factors=2)
        with pytest.raises(DMLCError, match="num_fields"):
            model.validate_batch(batch)
        SparseFFMModel(12, num_fields=5).validate_batch(batch)  # fits
        batch["field"][0] = -1  # negative sentinel: also clipped → error
        with pytest.raises(DMLCError, match="field ids"):
            SparseFFMModel(12, num_fields=5).validate_batch(batch)

    def test_libfm_file_to_ffm_training(self, tmp_path, rng):
        """End-to-end: libfm text → Parser → padded batch WITH field →
        FFM step — field[] flows to the device and is consumed."""
        from dmlc_tpu.models import SparseFFMModel
        ncol, nfields = 16, 4
        lines = []
        for i in range(200):
            nnz = rng.randint(1, 6)
            idx = np.sort(rng.choice(ncol, nnz, replace=False))
            toks = " ".join(
                f"{rng.randint(0, nfields)}:{j}:{rng.rand():.4f}"
                for j in idx)
            lines.append(f"{i % 2} {toks}")
        p = tmp_path / "d.libfm"
        p.write_text("\n".join(lines) + "\n")
        c = RowBlockContainer(np.uint32)
        parser = Parser.create(str(p), 0, 1, format="libfm")
        for b in parser:
            c.push_block(b)
        if hasattr(parser, "destroy"):
            parser.destroy()
        block = c.get_block()
        batch = pad_to_bucket(block, next_pow2_bucket(block.size),
                              next_pow2_bucket(block.nnz))
        assert "field" in batch
        model = SparseFFMModel(ncol, nfields, num_factors=2,
                               learning_rate=0.3)
        params = model.init_params()
        losses = []
        for _ in range(15):
            params, loss = model.train_step(params, batch)
            losses.append(float(loss))
        assert np.isfinite(losses[-1]) and losses[-1] <= losses[0]
