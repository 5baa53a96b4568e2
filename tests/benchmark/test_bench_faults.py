"""Each cell's run on the CPU, past the harness's look for a chip: sound,
it comes out correct; with the timed path broken underneath, once for
each fault the cell can have, and with the control in the program's
place, ``correct`` comes out false."""

import pytest

import bench_helpers as H
from benchmark import control, drive

# the control (the reference in bfloat16 in the program's place), then
# each fault the cell can have, planted by benchmark/control.py
CELLS = {"criteo_tb.stream_train": ("control", "unchanged", "half", "altered"),
         "higgs.load_resident": ("control", "half", "altered"),
         "criteo_tb.sharded_train4": ("control", "unchanged", "half", "exchange", "altered"),
         "criteo_tb.page_replay": ("control", "unchanged", "half", "altered"),
         **{c: ("control", "unchanged", "half", "altered", "unshuffled", "repeated", "twice")
            for c in H.SHUFFLED}}
# the check of rows out of source order that each of their faults fails
CAUGHT_BY = {"unshuffled": "rows_in_source_order", "repeated": "rows_repeated",
             "twice": "rows_repeated"}
# what each cell's newest finished epoch was served by: the in-order
# cells ask for the native parse; a traffic with steps before the parse
# and no stated engine leaves the engine to the program, which may serve
# it by either pair
SERVED = {"criteo_tb.stream_train": {("native", "native-padded")},
          "higgs.load_resident": {("native", "native-padded")},
          "criteo_tb.sharded_train4": {("native", "shard")},
          "criteo_tb.page_replay": {(None, "python-fused")},
          **{c: {("native", "native-padded"), ("python", "python-fused")}
             for c in H.SHUFFLED}}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return H.tiny_root(tmp_path_factory.mktemp("bench"))


def _run(root, cell, seed=2**31 + 3):
    r, cache = root
    # the shuffled cells may parse on the Python golden: a longer window
    # still finishes an epoch
    return drive.run(cell, seed, 1.0 if ".shuffled" in cell else 0.3, False, 0.0,
                     root=r, cache=cache, require_tpu=False)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_is_correct_when_sound(root, cell):
    out = _run(root, cell)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"ingest_gbps", "batch_wait_p95_ms", "setup_s"}
    assert list(out)[-1] == "checks"
    want = {"rows_mismatched", "epoch_rows_gap"}
    if "unchanged" in CELLS[cell]:
        want |= {"loss_gap", "grad_gap", "update_gap"}
    if cell in H.SHUFFLED:
        want |= {"rows_repeated", "rows_in_source_order"}
    assert set(out["checks"]) == want
    assert out["device"]["count"] == (4 if cell.endswith("4") else 1)
    # the device keys of rows out of order compile in set-up, as the rest
    assert out["info"]["compiles_in_window"] == 0
    if out["info"]["epochs_done"]:
        assert (out["info"]["engine"], out["info"]["assembly_path"]) in SERVED[cell]


@pytest.mark.parametrize("cell,fault", [(c, f) for c, fs in sorted(CELLS.items())
                                        for f in fs])
def test_fault_makes_the_run_incorrect(root, cell, fault):
    with control.planted(fault):
        out = _run(root, cell)
    assert not out["correct"], out["checks"]
    if fault in CAUGHT_BY:
        c = out["checks"][CAUGHT_BY[fault]]
        assert c["value"] > c["limit"], out["checks"]


@pytest.fixture(scope="module")
def served(root):
    """The engine the program serves the chunk-shuffled cell by, where
    its traffic states none."""
    out = _run(root, "criteo_tb.shuffled_chunks")
    assert out["correct"] and out["info"]["epochs_done"], out["checks"]
    assert "engine_served" not in out["checks"]
    return out["info"]["engine"]


@pytest.mark.parametrize("cell", sorted(H.STATED))
def test_a_stated_engine_is_a_check(root, served, cell):
    out = _run(root, cell)
    assert out["info"]["engine"] == served
    other = H.STATED[cell] != served
    assert out["checks"]["engine_served"] == {"value": int(other), "limit": 0}
    assert out["correct"] is not other, out["checks"]


def test_control_with_no_set_up_row_found_is_incorrect(root):
    # on this seed the control's rounding leaves no row of a set-up batch
    # with a content the corpus holds: each is replayed from its place in
    # source order, and the run still ends, incorrect
    with control.planted("control"):
        out = _run(root, "criteo_tb.shuffled_chunks", seed=3271828202)
    assert not out["correct"]
    assert out["checks"]["rows_mismatched"]["value"] > 0
    assert out["checks"]["loss_gap"]["value"] > out["checks"]["loss_gap"]["limit"]
