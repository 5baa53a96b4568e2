"""The program-span reduction (``benchmark/program_trace.py``) and its
figures by layer: exact attribution on a hand-built trace, the same on
spans recorded through the program's own span API under
``jax.profiler`` on the CPU, and the harness's own reduction of the
chip trace left as it was."""

import gzip
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import bench_helpers as H
from benchmark import program_trace, trace_reduce

FIGURES = ("assemble.device_idle_pct", "transfer.device_idle_pct",
           "transfer.host_ms_per_batch", "queue.epoch_boundary_ms",
           "queue.pull_p95_ms")


class Obj:
    def __init__(self, **kw):
        self.__dict__.update(kw)


def event(name, a, b, **stats):
    return Obj(name=name, start_ns=float(a), duration_ns=float(b - a),
               stats=list(stats.items()))


def device(ops):
    return Obj(name="/device:TPU:0",
               lines=[Obj(name="XLA Ops", events=[event("%op", a, b) for a, b in ops])])


@pytest.fixture(scope="module")
def probe_dir():
    import tempfile
    d = tempfile.mkdtemp()
    with gzip.open(os.path.join(H.DATA, "probe.xplane.pb.gz")) as f:
        with open(os.path.join(d, "probe.xplane.pb"), "wb") as g:
            g.write(f.read())
    return d


@pytest.fixture(scope="module")
def probe(probe_dir):
    return program_trace.reduce_dir(probe_dir)


def test_existing_outputs_unchanged_on_the_chip_trace(probe_dir):
    with open(os.path.join(H.DATA, "probe.reduced.json")) as f:
        before = json.load(f)
    assert trace_reduce.reduce_dir(probe_dir) == before


def test_chip_trace_has_no_program_spans(probe):
    assert probe["program"] == {}
    assert probe["program_other_idle_s"] == pytest.approx(probe["idle_s"])
    assert probe["program_coverage"] == 0.0
    assert probe["bench_wait_p95_ms"] > 0


@pytest.mark.parametrize("name", FIGURES)
def test_figure_is_none_without_program_spans(probe, name):
    assert program_trace.figures(probe)[name] is None


def test_program_attribution_on_a_built_trace():
    """Window 0..1000 ns on the consumer's line; A holds B holds C, A
    holds a terminal pull D (``end=1``); E lies outside the pull; the
    device runs over [0,120], [280,420] and [900,1000]."""
    s = 1e9  # ns → s
    host = Obj(name="python", events=[
        event("bench.window", 0, 1000),
        event("bench.next_batch", 100, 600),
        event("A", 100, 600, cat="pipeline"),
        event("B", 150, 300, cat="transfer"),
        event("C", 200, 250, cat="transfer"),
        event("D", 400, 450, cat="pipeline", end=1),
        event("E", 700, 800, cat="pipeline"),
        event("jax.internal", 120, 130),  # no cat: not the program's
    ])
    other_thread = Obj(name="python", events=[event("F", 0, 1000, cat="shard")])
    pd = Obj(planes=[Obj(name="/host:CPU", lines=[host, other_thread]),
                     device([(0, 120), (280, 420), (900, 1000)])])
    r = program_trace.reduce_profile(pd)
    prog = r["program"]
    assert set(prog) == {"A", "B", "C", "D.end", "E"}
    want = {  # name: (self, idle)
        "A": (300, 180), "B": (100, 80), "C": (50, 50), "D.end": (50, 30), "E": (100, 100)}
    for name, (self_ns, idle_ns) in want.items():
        assert prog[name]["count"] == 1
        assert prog[name]["self_s"] == pytest.approx(self_ns / s)
        assert prog[name]["idle_s"] == pytest.approx(idle_ns / s)
    assert prog["A"]["seconds"] == pytest.approx(500 / s)
    assert prog["B"]["p95_ms"] == pytest.approx(150 / 1e6)
    assert r["program_other_idle_s"] == pytest.approx(200 / s)
    assert r["idle_s"] == pytest.approx(640 / s)
    assert sum(p["idle_s"] for p in prog.values()) + r["program_other_idle_s"] \
        == pytest.approx(r["idle_s"])
    # of the pull's 500 ns, B, C and D (nested) hold 200
    assert r["program_coverage"] == pytest.approx(0.4)


def test_program_spans_clipped_to_the_window_and_averaged_over_chips():
    host = Obj(name="python", events=[
        event("bench.window", 100, 200),
        event("A", 50, 150, cat="pipeline"),
        event("B", 180, 260, cat="pipeline"),
    ])
    pd = Obj(planes=[Obj(name="/host:CPU", lines=[host]),
                     device([]), device([(100, 200)])])
    r = program_trace.reduce_profile(pd)
    assert r["program"]["A"]["seconds"] == pytest.approx(50 / 1e9)
    assert r["program"]["B"]["seconds"] == pytest.approx(20 / 1e9)
    # one chip idle throughout, the other busy: half of each span
    assert r["program"]["A"]["idle_s"] == pytest.approx(25 / 1e9)
    assert r["program_other_idle_s"] == pytest.approx(15 / 1e9)
    assert r["program_coverage"] is None  # no bench.next_batch


def _record_cpu_trace(log_dir):
    """Program spans opened through ``obs.trace`` while ``jax.profiler``
    collects: three pulls, each holding a put, between train steps."""
    import gc

    import jax

    from dmlc_tpu.obs import trace as obs_trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    gc.disable()  # no python.gc span inside a pull
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.next_batch"):
                    pull = obs_trace.begin("pull/to_device", "pipeline")
                    time.sleep(0.002)
                    put = obs_trace.begin("to_device.put", "transfer")
                    time.sleep(0.004)
                    put.end()
                    time.sleep(0.001)
                    pull.end()
                with jax.profiler.TraceAnnotation("bench.train_step"):
                    time.sleep(0.003)
    finally:
        jax.profiler.stop_trace()
        gc.enable()


def test_program_section_on_spans_recorded_on_the_cpu(tmp_path):
    import jax
    _record_cpu_trace(str(tmp_path))
    pd = jax.profiler.ProfileData.from_file(trace_reduce.find_xplane(str(tmp_path)))
    planes = list(pd.planes)
    host = {}
    for plane in planes:
        for line in plane.lines:
            for e in line.events:
                host.setdefault(e.name, []).append((e.start_ns, e.start_ns + e.duration_ns))
    assert len(host["pull/to_device"]) == len(host["to_device.put"]) == 3
    # the device runs exactly while each put is open
    r = program_trace.reduce_profile(Obj(planes=planes + [device(host["to_device.put"])]))
    prog = r["program"]
    pull, put = prog["pull/to_device"], prog["to_device.put"]
    assert pull["count"] == put["count"] == 3
    assert put["idle_s"] == pytest.approx(0.0, abs=1e-9)
    assert pull["self_s"] == pytest.approx(pull["seconds"] - put["seconds"])
    assert pull["idle_s"] == pytest.approx(pull["self_s"])
    assert put["idle_s"] + pull["idle_s"] + r["program_other_idle_s"] == pytest.approx(r["idle_s"])
    next_batch = sum(b - a for a, b in host["bench.next_batch"]) / 1e9
    assert r["program_coverage"] == pytest.approx(put["seconds"] / next_batch)
    assert 0 < r["program_coverage"] < 1  # about 4 ms of each 7 ms pull


PROGRAM = {
    "pull/to_device": {"count": 10, "seconds": 0.05, "self_s": 0.004, "p95_ms": 6.5, "idle_s": 0.004},
    "pull/assemble": {"count": 10, "seconds": 0.01, "self_s": 0.01, "p95_ms": 1.5, "idle_s": 0.01},
    "pull/cache": {"count": 2, "seconds": 0.002, "self_s": 0.002, "p95_ms": 1.0, "idle_s": 0.002},
    "to_device.stage": {"count": 10, "seconds": 0.006, "self_s": 0.006, "p95_ms": 0.7, "idle_s": 0.006},
    "to_device.put": {"count": 10, "seconds": 0.02, "self_s": 0.02, "p95_ms": 2.2, "idle_s": 0.02},
    "to_device.drain": {"count": 10, "seconds": 0.001, "self_s": 0.001, "p95_ms": 0.2, "idle_s": 0.0005},
    "pipeline.epoch_start": {"count": 2, "seconds": 0.03, "self_s": 0.003, "p95_ms": 15, "idle_s": 0.003},
    "pipeline.epoch_end": {"count": 2, "seconds": 0.001, "self_s": 0.001, "p95_ms": 0.5, "idle_s": 0.001},
}
SHARD = {
    "pull/shard": {"count": 4, "seconds": 0.08, "self_s": 0.002, "p95_ms": 21.0, "idle_s": 0.0},
    "shard.parse": {"count": 4, "seconds": 0.03, "self_s": 0.03, "p95_ms": 8, "idle_s": 0.001},
    "shard.stack": {"count": 4, "seconds": 0.01, "self_s": 0.01, "p95_ms": 3, "idle_s": 0.002},
    "shard.put": {"count": 4, "seconds": 0.04, "self_s": 0.04, "p95_ms": 11, "idle_s": 0.003},
}


@pytest.mark.parametrize("name, program, want", [
    ("assemble.device_idle_pct", PROGRAM, 100 * 0.012 / 2.0),
    ("assemble.device_idle_pct", SHARD, 100 * 0.003 / 2.0),
    ("transfer.device_idle_pct", PROGRAM, 100 * 0.0265 / 2.0),
    ("transfer.device_idle_pct", SHARD, 100 * 0.003 / 2.0),
    ("transfer.host_ms_per_batch", PROGRAM, 0.026 / 10 * 1e3),
    ("transfer.host_ms_per_batch", SHARD, 0.04 / 4 * 1e3),
    ("queue.epoch_boundary_ms", PROGRAM, 0.004 / 2 * 1e3),
    ("queue.pull_p95_ms", PROGRAM, 6.5),
    ("queue.pull_p95_ms", SHARD, 21.0),
])
def test_figures_on_recorded_input(name, program, want):
    red = {"program": program, "window_s": 2.0}
    assert program_trace.figures(red)[name] == pytest.approx(want)


def test_epoch_boundary_needs_an_epoch_end():
    assert program_trace.figures({"program": SHARD, "window_s": 2.0})["queue.epoch_boundary_ms"] is None


def test_bench_wait_pairs_each_pull_with_its_ready():
    """Three pulls in the window, each followed by its wait for the
    device; a pull before the window is left out."""
    host = Obj(name="python", events=[
        event("bench.next_batch", 0, 50), event("bench.block_until_ready", 50, 60),
        event("bench.window", 100, 1000),
        event("bench.next_batch", 100, 110), event("bench.block_until_ready", 110, 111),
        event("bench.next_batch", 300, 340), event("bench.block_until_ready", 340, 342),
        event("bench.next_batch", 600, 620), event("bench.block_until_ready", 620, 650),
    ])
    pd = Obj(planes=[Obj(name="/host:CPU", lines=[host]), device([(100, 1000)])])
    r = program_trace.reduce_profile(pd)
    assert r["bench_wait_p95_ms"] == pytest.approx(
        float(np.percentile([11, 42, 50], 95)) / 1e6)
    assert r["idle_s"] == 0.0
    assert r["program_coverage"] == 0.0


def test_script_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, "benchmark/program_trace.py", "--workload",
                        "criteo_tb.stream_train", "--seed", str(2**31 + 9), "--seconds", "1"],
                       cwd=H.REPO, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 3, p.stderr[-2000:]
    assert p.stdout == ""
    assert "no TPU" in p.stderr
