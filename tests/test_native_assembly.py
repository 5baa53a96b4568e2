"""ABI-5 native batch assembly (ISSUE 7): byte-parity of
``dtp_parser_next_padded`` with the Python fused golden across the edge
cases (empty rows, short last batch, mid-file schema flip,
release-after-EOF), padded-lease leak discipline (source arenas return
to the free list the moment a batch is cut), sharded single-file parse
byte-identity, and the double-buffered staging overlap proof
(device.xfer spans intersecting the next batch's to_device.stage)."""

import numpy as np
import pytest

from dmlc_tpu.data.parser import Parser
from dmlc_tpu.data.rowblock import RowBlockContainer
from dmlc_tpu.pipeline import Pipeline
from dmlc_tpu.utils.logging import DMLCError

from tests.test_native import _ensure_native

pytestmark = pytest.mark.skipif(not _ensure_native(),
                                reason="native engine not buildable")


def _write_libsvm(tmp_path, name="a.libsvm", rows=3000, seed=0,
                  qid_from=None, max_nnz=9, min_nnz=0):
    """libsvm corpus with zero-nnz rows and an optional mid-file qid
    schema flip (rows >= qid_from carry qid:)."""
    rng = np.random.RandomState(seed)
    lines = []
    for i in range(rows):
        nnz = rng.randint(min_nnz, max_nnz + 1)
        idx = np.sort(rng.choice(2000, nnz, replace=False))
        feats = " ".join(f"{j}:{v:.6f}" for j, v in zip(idx, rng.rand(nnz)))
        qid = (f"qid:{i // 50} " if qid_from is not None and i >= qid_from
               else "")
        lines.append(f"{(-1) ** i} {qid}{feats}".rstrip())
    p = tmp_path / name
    p.write_text("\n".join(lines) + "\n")
    return str(p)


def _write_libfm(tmp_path, rows=1500, seed=3):
    rng = np.random.RandomState(seed)
    lines = []
    for i in range(rows):
        nnz = rng.randint(1, 7)
        idx = np.sort(rng.choice(900, nnz, replace=False))
        feats = " ".join(f"{rng.randint(0, 12)}:{j}:{v:.6f}"
                         for j, v in zip(idx, rng.rand(nnz)))
        lines.append(f"{i % 2} {feats}")
    p = tmp_path / "a.libfm"
    p.write_text("\n".join(lines) + "\n")
    return str(p)


def _drain_padded(uri, engine, rows, nnz_bucket, fmt="libsvm",
                  chunk_size=32 << 10, parse_kw=None, **kw):
    """Run uri through parse(engine).batch(pad=True); return the list
    of deep-copied padded dicts plus the assembly_path the stage
    reported."""
    built = (Pipeline.from_uri(uri)
             .parse(format=fmt, engine=engine, chunk_size=chunk_size,
                    **(parse_kw or {}))
             .batch(rows, pad=True, nnz_bucket=nnz_bucket, **kw)
             .build())
    out = []
    for b in built:
        out.append({k: np.array(v, copy=True) for k, v in b.items()})
    snap = built.stats()
    built.close()
    path = None
    for st in snap["stages"]:
        path = st.get("extra", {}).get("assembly_path") or path
    return out, path


def _assert_batches_equal(native, python):
    assert len(native) == len(python)
    for i, (n, p) in enumerate(zip(native, python)):
        assert set(n.keys()) == set(p.keys()), f"batch {i} key set"
        for k in p:
            np.testing.assert_array_equal(
                np.asarray(n[k]), np.asarray(p[k]),
                err_msg=f"batch {i} key {k}")
            assert np.asarray(n[k]).dtype == np.asarray(p[k]).dtype, \
                f"batch {i} key {k} dtype"


class TestPaddedParity:
    """Native next_padded vs the Python fused golden, batch for batch:
    same key sets, dtypes, and bytes — the ABI-5 parity pin."""

    def test_libsvm_with_empty_rows_and_short_last(self, tmp_path):
        # min_nnz=0 exercises empty rows; 3000 % 128 != 0 exercises the
        # short last batch (num_rows < rows under the padding)
        uri = _write_libsvm(tmp_path, rows=3000, min_nnz=0)
        nat, nat_path = _drain_padded(uri, "native", 128, 128 * 12)
        py, py_path = _drain_padded(uri, "python", 128, 128 * 12)
        assert nat_path == "native-padded", \
            "batch() on a native parse must lower onto the engine"
        assert py_path == "python-fused"
        _assert_batches_equal(nat, py)
        last = nat[-1]
        assert int(last["num_rows"]) == 3000 % 128  # really short
        assert last["label"].shape == nat[0]["label"].shape  # same bucket

    def test_qid_schema_flip_mid_file(self, tmp_path):
        uri = _write_libsvm(tmp_path, rows=2000, qid_from=1000)
        nat, nat_path = _drain_padded(uri, "native", 100, 100 * 12)
        py, _ = _drain_padded(uri, "python", 100, 100 * 12)
        assert nat_path == "native-padded"
        _assert_batches_equal(nat, py)
        assert any("qid" in b for b in nat)

    def test_want_qid_forces_presence_everywhere(self, tmp_path):
        uri = _write_libsvm(tmp_path, rows=600, qid_from=None)
        nat, _ = _drain_padded(uri, "native", 64, 64 * 12, want_qid=True)
        py, _ = _drain_padded(uri, "python", 64, 64 * 12, want_qid=True)
        _assert_batches_equal(nat, py)
        assert all("qid" in b and np.all(np.asarray(b["qid"]) == -1)
                   for b in nat)

    def test_libfm_field_parity(self, tmp_path):
        uri = _write_libfm(tmp_path)
        nat, nat_path = _drain_padded(uri, "native", 96, 96 * 8,
                                      fmt="libfm")
        py, _ = _drain_padded(uri, "python", 96, 96 * 8, fmt="libfm")
        assert nat_path == "native-padded"
        _assert_batches_equal(nat, py)
        assert all("field" in b for b in nat)

    def test_csv_parity(self, tmp_path):
        rng = np.random.RandomState(7)
        lines = [f"{i % 2}," + ",".join(f"{v:.5f}" for v in rng.rand(6))
                 for i in range(1100)]
        p = tmp_path / "a.csv"
        p.write_text("\n".join(lines) + "\n")
        nat, nat_path = _drain_padded(str(p), "native", 80, 80 * 8,
                                      fmt="csv")
        py, _ = _drain_padded(str(p), "python", 80, 80 * 8, fmt="csv")
        assert nat_path == "native-padded"
        _assert_batches_equal(nat, py)

    def test_row_bucket_wider_than_rows(self, tmp_path):
        uri = _write_libsvm(tmp_path, rows=500)
        nat, _ = _drain_padded(uri, "native", 64, 64 * 12, row_bucket=96)
        py, _ = _drain_padded(uri, "python", 64, 64 * 12, row_bucket=96)
        _assert_batches_equal(nat, py)
        assert nat[0]["label"].shape[-1] == 96

    def test_blank_only_file_yields_nothing(self, tmp_path):
        # chunks that parse to ZERO rows (blank lines) must not emit an
        # empty padded batch — the stream ends with None, no lease held
        from dmlc_tpu.native.bindings import NativeLibSVMParser
        p = tmp_path / "blank.libsvm"
        p.write_bytes(b"\n\n\n")
        parser = NativeLibSVMParser(str(p), 0, 1)
        assert parser.next_padded(64, 64, 512) is None
        assert parser.outstanding() == 0
        parser.destroy()

    def test_blank_runs_between_rows_parity(self, tmp_path):
        p = tmp_path / "gaps.libsvm"
        p.write_text("\n".join(
            ("" if i % 3 else f"{i % 2} {i % 40}:{i}.25")
            for i in range(400)) + "\n")
        nat, _ = _drain_padded(str(p), "native", 32, 64)
        py, _ = _drain_padded(str(p), "python", 32, 64)
        _assert_batches_equal(nat, py)


class TestPaddedLease:
    """Lease lifetime and the leak probe: padded emission must hand the
    source CSR arenas straight back to the free list (the PR 2
    RSS-retention class), with the padded block the ONLY outstanding
    lease."""

    def _parser(self, tmp_path, rows=1200):
        from dmlc_tpu.native.bindings import NativeLibSVMParser
        uri = _write_libsvm(tmp_path, rows=rows, name="lease.libsvm")
        return NativeLibSVMParser(uri, 0, 1, chunk_size=8 << 10)

    def test_arena_returns_to_free_list_after_emission(self, tmp_path):
        parser = self._parser(tmp_path)
        n_batches = 0
        while True:
            b = parser.next_padded(64, 64, 64 * 12)
            if b is None:
                break
            n_batches += 1
            # the padded lease is the ONLY thing outstanding: every
            # source arena the batch was cut from is back in the pool
            # even while the batch's views are live
            assert parser.outstanding() == 1
        assert n_batches >= 10
        # EOF released the last padded lease too
        assert parser.outstanding() == 0
        parser.destroy()

    def test_release_after_eof(self, tmp_path):
        parser = self._parser(tmp_path, rows=900)
        held = []
        while True:
            b = parser.next_padded(64, 64, 64 * 12)
            if b is None:
                break
            snap = {k: np.array(v, copy=True) for k, v in b.items()}
            held.append((snap, b, parser.detach()))
        assert len(held) >= 2
        assert parser.next_padded(64, 64, 64 * 12) is None  # EOF sticky
        # every detached padded block survives EOF byte-for-byte
        assert parser.outstanding() == len(held)
        for snap, b, _lease in held:
            for k, v in snap.items():
                np.testing.assert_array_equal(np.asarray(b[k]), v)
        for _snap, _b, lease in held:
            lease.release()
        assert parser.outstanding() == 0
        parser.destroy()

    def test_mode_guard_next_then_padded(self, tmp_path):
        parser = self._parser(tmp_path, rows=400)
        assert parser.next()
        with pytest.raises(DMLCError, match="before_first"):
            parser.next_padded(64, 64, 64 * 12)
        parser.destroy()

    def test_before_first_recycles_carry(self, tmp_path):
        # a partially consumed arena (the padded carry) goes back to
        # the pool on before_first and the re-read stream is intact
        from dmlc_tpu.native.bindings import NativeLibSVMParser
        uri = _write_libsvm(tmp_path, rows=1000, name="carry.libsvm")
        parser = NativeLibSVMParser(uri, 0, 1, chunk_size=8 << 10)
        assert parser.next_padded(32, 32, 32 * 12) is not None
        parser.before_first()
        assert parser.outstanding() == 0
        c = RowBlockContainer(np.uint32)
        while parser.next():
            c.push_block(parser.value())
        parser.destroy()
        ref = RowBlockContainer(np.uint32)
        p = Parser.create(uri, 0, 1, format="libsvm", engine="python")
        for blk in p:
            ref.push_block(blk)
        assert c.get_block().content_hash() == ref.get_block().content_hash()


def _hash_parse(uri, engine, fmt="libsvm", **kw):
    c = RowBlockContainer(np.uint32)
    p = Parser.create(uri, 0, 1, format=fmt, engine=engine, **kw)
    for b in p:
        c.push_block(b)
    if hasattr(p, "destroy"):
        p.destroy()
    return c.get_block().content_hash()


class TestShardedSingleFile:
    """shards=N splits ONE file across N native parsers on aligned
    byte ranges; the reassembled stream must be byte-identical to the
    1-parser stream (and the python golden)."""

    def test_byte_identity_vs_one_parser(self, tmp_path):
        uri = _write_libsvm(tmp_path, rows=6000, name="big.libsvm")
        base = _hash_parse(uri, "native", chunk_size=16 << 10)
        assert base == _hash_parse(uri, "python")
        for shards in (2, 3, 4):
            assert _hash_parse(uri, "native", shards=shards,
                               chunk_size=16 << 10) == base, \
                f"shards={shards} reordered or corrupted the stream"

    def test_dispatch_returns_sharded_parser(self, tmp_path):
        from dmlc_tpu.native.bindings import NativeShardedTextParser
        uri = _write_libsvm(tmp_path, rows=300)
        p = Parser.create(uri, 0, 1, format="libsvm", engine="native",
                          shards=2)
        assert isinstance(p, NativeShardedTextParser)
        p.destroy()

    def test_tiny_file_more_shards_than_content(self, tmp_path):
        # shards beyond the file's aligned ranges leave trailing
        # sub-parsers empty; the stream is still exactly the input
        uri = _write_libsvm(tmp_path, rows=40, name="tiny.libsvm")
        assert (_hash_parse(uri, "native", shards=8)
                == _hash_parse(uri, "python"))

    def test_nested_split_runs_unsharded(self, tmp_path):
        # under an outer part/num_parts split, shards= is a no-op (the
        # alignment rule must not apply twice) — parity per part
        uri = _write_libsvm(tmp_path, rows=2000, name="parts.libsvm")
        for k in (0, 1):
            c1 = RowBlockContainer(np.uint32)
            p = Parser.create(uri, k, 2, format="libsvm", engine="native")
            for b in p:
                c1.push_block(b)
            p.destroy()
            c2 = RowBlockContainer(np.uint32)
            p = Parser.create(uri, k, 2, format="libsvm", engine="native",
                              shards=4)
            for b in p:
                c2.push_block(b)
            p.destroy()
            assert c1.get_block().content_hash() == \
                c2.get_block().content_hash()

    def test_sharded_padded_parity(self, tmp_path):
        # sharded parse under padded assembly (ABI 6): the GANG handle
        # cuts padded batches across the sub-parsers' shard-ordered
        # arena streams in C (dtp_gang_next_padded), so the lowering
        # fuses — assembly_path is native-padded — and batches stay
        # byte-identical to the unsharded python golden (a batch MAY
        # straddle the shard boundary; the gang cuts it exactly where
        # the 1-parser stream would)
        uri = _write_libsvm(tmp_path, rows=4000, name="sp.libsvm")
        nat, nat_path = _drain_padded(uri, "native", 128, 128 * 12,
                                      chunk_size=16 << 10,
                                      parse_kw={"shards": 3})
        py, _ = _drain_padded(uri, "python", 128, 128 * 12)
        assert nat_path == "native-padded"
        _assert_batches_equal(nat, py)


class TestSteadyPathEndToEnd:
    """Padded leases must survive the downstream stages: prefetch
    detaches them (release-on-next-pull), to_device routes the batch
    through a staging slot and frees the lease at copy time."""

    def test_padded_through_prefetch_parity(self, tmp_path):
        uri = _write_libsvm(tmp_path, rows=2000)
        built = (Pipeline.from_uri(uri)
                 .parse(format="libsvm", engine="native",
                        chunk_size=32 << 10)
                 .batch(100, pad=True, nnz_bucket=100 * 12)
                 .prefetch(depth=3).build())
        nat = [{k: np.array(v, copy=True) for k, v in b.items()}
               for b in built]
        snap = built.stats()
        built.close()
        path = None
        for st in snap["stages"]:
            path = st.get("extra", {}).get("assembly_path") or path
        assert path == "native-padded"
        py, _ = _drain_padded(uri, "python", 100, 100 * 12)
        _assert_batches_equal(nat, py)

    def test_map_between_parse_and_batch_unfuses(self, tmp_path):
        # a map stage between the native parse and batch() blocks the
        # fusion: the same native parse, assembled by the Python fused
        # pad instead — and the batches stay byte-identical
        uri = _write_libsvm(tmp_path, rows=2000)
        built = (Pipeline.from_uri(uri)
                 .parse(format="libsvm", engine="native",
                        chunk_size=32 << 10)
                 .map(lambda b: b, name="unfuse")
                 .batch(100, pad=True, nnz_bucket=100 * 12).build())
        unfused = [{k: np.array(v, copy=True) for k, v in b.items()}
                   for b in built]
        snap = built.stats()
        built.close()
        path = None
        for st in snap["stages"]:
            path = st.get("extra", {}).get("assembly_path") or path
        assert path == "python-fused"
        py, _ = _drain_padded(uri, "python", 100, 100 * 12)
        _assert_batches_equal(unfused, py)

    def test_full_steady_path_to_device(self, tmp_path):
        uri = _write_libsvm(tmp_path, rows=1500)
        built = (Pipeline.from_uri(uri)
                 .parse(format="libsvm", engine="native",
                        chunk_size=32 << 10)
                 .batch(128, pad=True, nnz_bucket=128 * 12)
                 .to_device(window=2).build())
        dev = [{k: np.asarray(v) for k, v in b.items()} for b in built]
        built.close()
        py, _ = _drain_padded(uri, "python", 128, 128 * 12)
        # values only: jax with x64 off canonicalizes int64 device
        # arrays to int32, so the device batches' dtypes legitimately
        # differ from the host layout
        assert len(dev) == len(py)
        for i, (d, p) in enumerate(zip(dev, py)):
            assert set(d.keys()) == set(p.keys()), f"batch {i} key set"
            for k in p:
                np.testing.assert_array_equal(
                    d[k], np.asarray(p[k]), err_msg=f"batch {i} key {k}")


class TestStagingOverlap:
    """Double-buffered staging: batch N's H2D window must overlap batch
    N+1's staged assembly on one trace timeline — THE acceptance
    criterion's span-intersection assert."""

    def _batches(self, n=6, side=192):
        return [{"x": np.full((side, side), i, np.float32),
                 "y": np.full((side,), i, np.float32)} for i in range(n)]

    def test_xfer_overlaps_next_assemble(self):
        from dmlc_tpu.obs import trace as obs_trace
        from dmlc_tpu.parallel.device_iter import device_prefetch
        batches = self._batches()
        rec = obs_trace.start()
        try:
            out = list(device_prefetch(iter(batches), size=2,
                                       staging=True))
        finally:
            obs_trace.stop()
        assert len(out) == len(batches)
        spans = {"device.xfer": [], "to_device.stage": []}
        for ph, name, _cat, t, d, _tid, _args in rec.events():
            if ph == "X" and name in spans:
                spans[name].append((t, t + d))
        assert len(spans["device.xfer"]) == len(batches)
        assert len(spans["to_device.stage"]) == len(batches)
        # non-empty intersection with an assemble that STARTED inside
        # the transfer's enqueue→ready window: the overlap is real, not
        # a pair of adjacent spans
        overlapping = [
            (x, a)
            for x in spans["device.xfer"]
            for a in spans["to_device.stage"]
            if x[0] < a[0] < x[1]
        ]
        assert overlapping, \
            "no H2D transfer window overlapped a later staged assembly"

    def test_staged_batches_faithful(self):
        from dmlc_tpu.parallel.device_iter import device_prefetch
        batches = self._batches(n=5, side=32)
        out = list(device_prefetch(iter(batches), size=2, staging=True))
        assert len(out) == 5
        for i, b in enumerate(out):
            np.testing.assert_array_equal(np.asarray(b["x"]),
                                          batches[i]["x"])
            np.testing.assert_array_equal(np.asarray(b["y"]),
                                          batches[i]["y"])

    def test_slot_reuse_and_gauge(self):
        from dmlc_tpu.obs.metrics import REGISTRY
        from dmlc_tpu.parallel.device_iter import HostStaging
        pool = HostStaging(slots=2, alias_unsafe=False)
        a = {"x": np.arange(64, dtype=np.float32)}
        s1 = pool.stage(a)
        assert s1["x"] is not a["x"]
        np.testing.assert_array_equal(s1["x"], a["x"])
        assert pool.in_flight == 1
        assert REGISTRY.gauge("device.staging").value == 1
        pool.release(s1)
        assert REGISTRY.gauge("device.staging").value == 0
        # fixed-shape steady state: the SAME buffer serves batch 2
        s2 = pool.stage({"x": np.zeros(64, np.float32)})
        assert s2["x"] is s1["x"]
        pool.release(s2)

    def test_alias_unsafe_never_reuses(self):
        from dmlc_tpu.parallel.device_iter import HostStaging
        pool = HostStaging(slots=2, alias_unsafe=True)
        a = {"x": np.arange(16, dtype=np.float32)}
        s1 = pool.stage(a)
        pool.release(s1)
        s2 = pool.stage(a)
        assert s2["x"] is not s1["x"]  # consumer may alias s1's memory
        pool.release(s2)
