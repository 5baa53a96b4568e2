"""Rows out of source order: the locator that finds each delivered row
in the corpus by its content, the multiset of repeated rows, the bytes
counted under a shuffle, the engine and assembly path a run names, and
the in-order path pinned to what it read before the out-of-order
read-back came in."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_helpers as H
from benchmark import control, drive, locate, ref
from benchmark.gen import ensure_corpus

# Each existing cell on the tiny root under a clock that moves 1/1024 s
# a call: 40 window batches. Read from the harness as it was before
# rows out of source order were read back (same root, seeds and clock).
PINNED = {
    "criteo_tb.stream_train": {"checks": {"epoch_rows_gap": 0, "rows_mismatched": 0, "loss_gap": 4.327044625036353e-07, "grad_gap": 3.132661929274655e-08, "update_gap": 6.831254411439954e-08}, "attempted": 40, "window_s": 0.1181640625, "window_bytes": 1469109, "batches": 40, "epochs_done": 2, "corpus_bytes": 620992, "corpus_rows": 2100, "compiles_in_window": 0},  # noqa: E501
    "higgs.load_resident": {"checks": {"epoch_rows_gap": 0, "rows_mismatched": 0}, "attempted": 40, "window_s": 0.1181640625, "window_bytes": 3661416, "batches": 40, "epochs_done": 3, "corpus_bytes": 1095793, "corpus_rows": 1500, "compiles_in_window": 0},  # noqa: E501
    "criteo_tb.sharded_train4": {"checks": {"epoch_rows_gap": 0, "rows_mismatched": 0, "loss_gap": 4.327044625036353e-07, "grad_gap": 7.474219613759137e-12, "update_gap": 2.1321209153053033e-08}, "attempted": 40, "window_s": 0.1181640625, "window_bytes": 4967936, "batches": 40, "epochs_done": 8, "corpus_bytes": 620992, "corpus_rows": 2100, "compiles_in_window": 0},  # noqa: E501
    "criteo_tb.page_replay": {"checks": {"epoch_rows_gap": 0, "rows_mismatched": 0, "loss_gap": 4.327044625036353e-07, "grad_gap": 5.795661046702931e-08, "update_gap": 2.117756682685924e-08}, "attempted": 40, "window_s": 0.1181640625, "window_bytes": 1469215, "batches": 40, "epochs_done": 2, "corpus_bytes": 620992, "corpus_rows": 2100, "compiles_in_window": 0},  # noqa: E501
}
SEEDS = {"criteo_tb.stream_train": 2**31 + 41, "higgs.load_resident": 2**31 + 42,
         "criteo_tb.sharded_train4": 2**31 + 43, "criteo_tb.page_replay": 2**31 + 44}


class Clock:
    """``time`` for the harness: ``perf_counter`` moves 1/1024 s a call,
    so a window lasts a fixed number of pulls."""

    def __init__(self):
        self.t = 0.0

    def perf_counter(self):
        self.t += 2.0 ** -10
        return self.t


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return H.tiny_root(tmp_path_factory.mktemp("order"))


@pytest.mark.parametrize("cell", sorted(PINNED))
def test_in_order_readings_are_unchanged(root, cell, monkeypatch):
    monkeypatch.setattr(drive, "time", Clock())
    r = drive.run(cell, SEEDS[cell], 120 * 2.0 ** -10, False, 0.0, root=root[0],
                  cache=root[1], require_tpu=False)
    window_bytes = (r["metrics"]["ingest_gbps"]["value"] * r["info"]["window_s"]
                    * r["device"]["count"] * 1e9)
    got = {"checks": {k: c["value"] for k, c in r["checks"].items()},
           "attempted": r["attempted"], "window_s": r["info"]["window_s"],
           "window_bytes": round(window_bytes),
           **{k: r["info"][k] for k in ("batches", "epochs_done", "corpus_bytes",
                                        "corpus_rows", "compiles_in_window")}}
    assert got == PINNED[cell]
    # an in-order run neither builds nor reads the locator
    assert "locator_s" not in r["info"]
    assert not any(f.startswith("locator") for _, _, fs in os.walk(root[1]) for f in fs)


@pytest.mark.parametrize("name", ["criteo_tb_libsvm", "higgs_csv"])
def test_locator_finds_each_row_by_its_parsed_text(tmp_path, name):
    cfg = H.config(name)
    corpus, _ = ensure_corpus(cfg, str(tmp_path))
    loc, info = locate.ensure_locator(cfg, corpus)
    assert info["built"]
    assert not locate.ensure_locator(cfg, corpus)[1]["built"]
    stream = corpus.stream(2**31 + 5)
    rows = ref.parser(cfg["parse"]["format"])(stream.lines(0, stream.rows), **cfg["parse"])
    keys = locate.row_keys(rows)
    assert len(np.unique(keys)) == stream.rows
    first = stream.part_first_rows(2)
    for d in range(2):
        lo, hi = int(first[d]), int(first[d + 1])
        got = loc.candidates(stream, keys[lo:hi], lo, hi)
        assert [c.tolist() for c in got] == [[r] for r in range(lo, hi)]
        # a row of the other chip's part is not found in this one
        other = keys[first[1 - d]:first[2 - d]][:5]
        assert all(c.size == 0 for c in loc.candidates(stream, other, lo, hi))
    # the key follows the content: a value, an index or the label moved
    for col, at in (("value", 7), ("index", 3), ("label", 0)):
        bad = dict(rows, **{col: rows[col].copy()})
        bad[col][at] += 1
        assert locate.row_keys(bad)[0] != keys[0]
    # the device takes the same keys of a padded batch, alone or on a mesh
    width = max(rows["length"])
    some = [ref.parser(cfg["parse"]["format"])(stream.lines_at(np.array(r)), **cfg["parse"])
            for r in ([0, 1, 2], [5, 3], [9, 8, 7, 6])]
    batches = [_padded(r, 8, 8 * width) for r in some]
    keyfn = jax.jit(locate.device_keys)
    lanes = np.asarray(keyfn({k: jnp.asarray(v) for k, v in batches[0].items()}))
    assert lanes[:3].tolist() == locate.first_lane(keys[:3]).tolist()
    lanes = np.asarray(keyfn({k: jnp.stack([b[k] for b in batches]) for k in batches[0]}))
    for d, r in enumerate(some):
        assert (lanes[d][:len(r["length"])].tolist()
                == locate.first_lane(locate.row_keys(r)).tolist())


def _padded(rows, bucket, nnz):
    n, z = len(rows["length"]), int(rows["length"].sum())
    off = np.concatenate([[0], np.cumsum(rows["length"])])

    def pad(a, size):
        return np.concatenate([a, np.zeros(size - len(a), a.dtype)])

    return {"offset": np.concatenate([off, np.full(bucket - n, z)]).astype(np.int32),
            "label": pad(rows["label"], bucket), "weight": pad(rows["weight"], bucket),
            "index": pad(rows["index"].astype(np.int32), nnz),
            "value": pad(rows["value"], nnz),
            "num_rows": np.int32(n), "num_nnz": np.int32(z)}


def test_repeats_are_counted_as_a_multiset(tmp_path):
    cfg = H.config("criteo_tb_libsvm")
    corpus, _ = ensure_corpus(cfg, str(tmp_path))
    loc, _ = locate.ensure_locator(cfg, corpus)
    stream = corpus.stream(3)
    first = stream.part_first_rows(1)
    parse = ref.parser("libsvm")
    a = parse(stream.lines(100, 110))
    b = parse(stream.lines_at(np.array([104, 103, 500])))
    samples = {0: _padded(a, 16, 16 * 39), 1: _padded(b, 16, 16 * 39),
               2: _padded(b, 16, 16 * 39)}
    epochs = np.array([0, 0, 1])
    # where source order would put each batch's rows
    lo = np.array([[0], [10], [13]])
    found = drive.locate_rows(loc, stream, first, samples, epochs, lo)
    assert found[0, 0].tolist() == list(range(100, 110))
    assert found[1, 0].tolist() == found[2, 0].tolist() == [104, 103, 500]
    # every delivered row's key, as the device takes them: rows 104 and
    # 103 again in epoch 0; epoch 1 starts anew
    keys = np.zeros((3, 1, 16), np.uint32)
    num_rows = np.array([[10], [3], [3]])
    for i, rows in enumerate((a, b, b)):
        keys[i, 0, :num_rows[i, 0]] = locate.first_lane(locate.row_keys(rows))
    assert loc.excess(stream, first, keys, num_rows, epochs) == 2
    # two source rows with one content: the second delivery takes the second
    by_canon = np.empty(len(loc.keys), np.uint64)
    by_canon[loc.rows] = loc.keys
    canon = loc._canon(stream, np.arange(stream.rows))
    by_canon[canon[[1000, 1001]]] = by_canon[canon[[104, 103]]]
    order = np.argsort(by_canon, kind="stable")
    twin = locate.Locator(by_canon[order], order, loc.part_first)
    assert twin.excess(stream, first, keys, num_rows, epochs) == 0
    got = drive.locate_rows(twin, stream, first, samples, epochs, lo)
    assert got[1, 0].tolist() == [1000, 1001, 500]
    # a content no row of the part holds counts at each delivery; two
    # chips' parts hold each row once between them
    keys[2, 0, 0] += np.uint32(1)
    assert twin.excess(stream, first, keys, num_rows, epochs) == 1
    halves = stream.part_first_rows(2)
    split = np.zeros((1, 2, 16), np.uint32)
    split[0, 0, 0] = keys[0, 0, 0]
    split[0, 1, 0] = locate.first_lane(locate.row_keys(
        parse(stream.lines(int(halves[1]), int(halves[1]) + 1))))[0]
    assert loc.excess(stream, halves, split, np.array([[1, 1]]), np.array([0])) == 0
    split[0, 1, 0] = split[0, 0, 0]  # chip 0's row on chip 1
    assert loc.excess(stream, halves, split, np.array([[1, 1]]), np.array([0])) == 1
    # a row that no source line holds takes the row source order puts in
    # its place, and the exact comparison counts it
    samples[1]["value"][0] += 1
    got = drive.locate_rows(loc, stream, first, samples, epochs, lo)
    assert got[1, 0].tolist() == [10, 103, 500]
    assert ref.rows_mismatched(ref.device_rows(samples[1]),
                               parse(stream.lines_at(got[1, 0]))) == 1


@pytest.mark.parametrize("nparts", [1, 3])
def test_finished_epochs_count_their_parts_bytes_exactly(tmp_path, nparts):
    corpus, _ = ensure_corpus(H.config("criteo_tb_libsvm"), str(tmp_path))
    stream = corpus.stream(9)
    first = stream.part_first_rows(nparts)
    rng = np.random.default_rng(0)
    blocks, epochs = [], []
    for e in range(3):
        # an epoch: each part's rows cut into batches of uneven size
        cuts = [np.diff(np.concatenate([[0], np.sort(rng.choice(np.arange(1, n), 20, False)), [n]]))
                for n in np.diff(first)]
        blocks.append(np.stack(cuts, 1))
        epochs += [e] * 21
    blocks.append(np.full((4, nparts), 5))  # the unfinished epoch: 20 rows a part
    epochs += [3] * 4
    got = drive.epoch_bytes(np.concatenate(blocks), np.asarray(epochs), 3, stream, first)
    assert got[:3] == [stream.total_bytes] * 3
    part_bytes = stream.start[first[1:]] - stream.start[first[:-1]]
    assert got[3] == pytest.approx(float((20 * part_bytes / np.diff(first)).sum()))


# the stats snapshot of an epoch parsed by the Python golden
GOLDEN = {"stages": [{"kind": "parse", "extra": {"bytes_read": 1}},
                     {"kind": "assemble", "extra": {"assembly_path": "python-fused"}}]}


def test_served_by_names_the_engine_and_the_assembly_path():
    with open(os.path.join(H.DATA, "stats.json")) as f:
        stats = json.load(f)
    assert drive.served_by(stats["stream_train"]["stats"][0]) == {
        "engine": "native", "assembly_path": "native-padded"}
    assert drive.served_by(stats["page_replay"]["stats"][0]) == {
        "engine": None, "assembly_path": "python-fused", "replay_tier": "pages"}
    assert drive.served_by(GOLDEN) == {"engine": "python", "assembly_path": "python-fused"}
    assert drive.served_by(None) == {"engine": None, "assembly_path": None}


@pytest.mark.parametrize("stated,epochs,want", [
    (None, ["native"], None),        # no stated engine, no check
    ("native", ["native", "native"], 0),
    ("python", ["golden"], 0),
    ("native", ["native", "golden"], 1),  # one epoch served by another
    ("python", ["native"], 1),
    ("native", [], 1),               # no finished epoch shows its engine
])
def test_engine_served_reads_every_finished_epoch(stated, epochs, want):
    with open(os.path.join(H.DATA, "stats.json")) as f:
        native = json.load(f)["stream_train"]["stats"][0]
    snaps = [native if e == "native" else GOLDEN for e in epochs]
    traffic = {"consumer": "sgd"} if stated is None else {"consumer": "sgd", "engine": stated}
    assert drive.engine_served(traffic, snaps) == want


def test_repeated_plant_repeats_the_first_row_of_each_shard():
    parse = ref.parser("libsvm")
    lines = [b"1 1:2 5:3", b"0 2:1", b"1 3:4 4:5 6:1", b"0 7:7"]
    shards = [_padded(parse(lines[:3]), 4, 8), _padded(parse(lines[1:]), 4, 8)]
    b = {k: np.stack([s[k] for s in shards]) for k in shards[0]}
    out = control._repeat_first(b)
    for d, src in enumerate((lines[:3], lines[1:])):
        got = ref.device_rows({k: v[d] for k, v in out.items()})
        want = parse([src[0], src[0], src[2]])
        want.update(pad_weight=0.0, nnz_end=got["nnz"], nnz=got["nnz"])
        assert ref.rows_mismatched(got, want) == 0


@pytest.mark.parametrize("order", ["in_order", "chunks"])
def test_twice_plant_reads_the_first_sub_shard_again(tmp_path, order):
    # a source of three files: under the plant, the program reads the
    # first file's rows a second time and the last file's never, on the
    # native engine in source order and on whichever engine the program
    # picks under a chunk shuffle
    from collections import Counter

    from dmlc_tpu.pipeline import Pipeline
    files = []
    for k in range(3):
        files.append(str(tmp_path / f"part-{k}.txt"))
        with open(files[-1], "wb") as f:
            f.write(b"".join(b"1 %d:1\n" % (100 * k + i) for i in range(100)))

    def rows(pipe):
        built = (pipe.shuffle(4, seed=7).parse(format="libsvm") if order == "chunks"
                 else pipe.parse(format="libsvm", engine="native")).build()
        try:
            return Counter(int(i) for block in built for i in block.index)
        finally:
            built.close()

    assert rows(Pipeline.from_uri(";".join(files))) == Counter(range(300))
    with control.planted("twice"):
        got = rows(Pipeline.from_uri(";".join(files)))
    assert got == Counter({**{i: 2 for i in range(100)}, **{i: 1 for i in range(100, 200)}})
    # the program's entry is restored as the plant ends
    assert rows(Pipeline.from_uri(";".join(files))) == Counter(range(300))
