"""Program spans on the profiler's clock (dmlc_tpu.obs.trace.begin):
which spans a pipeline run puts on the ``jax.profiler`` timeline, that
nothing is built while no profiler collects, that delivery reads no
device array back for its telemetry, and the shard stage's epoch
extras."""

import gc
import glob
import os
import threading
from collections import Counter

import numpy as np
import pytest

from dmlc_tpu.obs import trace as obs_trace
from dmlc_tpu.pipeline import Pipeline


def _write_libsvm(tmp_path, rows=1000, seed=0):
    rng = np.random.RandomState(seed)
    lines = []
    for i in range(rows):
        idx = np.sort(rng.choice(300, rng.randint(2, 7), replace=False))
        lines.append(f"{i % 2} " + " ".join(f"{j}:{v:.3f}" for j, v in
                                            zip(idx, rng.rand(len(idx)))))
    p = tmp_path / "d.libsvm"
    p.write_text("\n".join(lines) + "\n")
    return str(p)


def _profiled(log_dir, fn):
    """Run ``fn`` while jax.profiler collects; the program's spans in
    the trace: name → list of their event stats."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)[0]
    spans = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                stats = dict(e.stats)
                if "cat" in stats:
                    spans.setdefault(e.name, []).append(stats)
    return spans


def _padded(uri, **kw):
    return (Pipeline.from_uri(uri).parse(format="libsvm", engine="python")
            .batch(128, pad=True, nnz_bucket=128 * 8).to_device(**kw))


def test_pipeline_spans_on_the_profiler_timeline(tmp_path):
    uri = _write_libsvm(tmp_path)
    built = _padded(uri).build()
    batches = []

    def run():
        for _ in range(2):
            batches.append(sum(1 for _ in built))

    spans = _profiled(str(tmp_path / "prof"), run)
    built.close()
    n = sum(batches)
    assert batches == [8, 8]
    pulls = Counter("end" in s for s in spans["pull/to_device"])
    assert pulls == {False: n, True: 2}  # one terminal pull an epoch
    for name in ("to_device.stage", "to_device.put", "to_device.drain"):
        assert len(spans[name]) == n, name
        assert {s["cat"] for s in spans[name]} == {"transfer"}
    assert len(spans["pipeline.epoch_start"]) == 2
    assert len(spans["pipeline.epoch_end"]) == 2
    assert {s["cat"] for s in spans["pull/assemble"]} == {"pipeline"}
    assert "device.xfer" not in spans  # recorder only: it overlaps


def test_shard_spans_on_a_one_device_mesh(tmp_path):
    import jax
    from jax.sharding import Mesh
    uri = _write_libsvm(tmp_path, rows=640)
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    built = (Pipeline.from_uri(uri).parse(format="libsvm", engine="python")
             .shard(mesh, row_bucket=128, nnz_bucket=1 << 11,
                    steady_replay=False).build())
    rounds = []
    spans = _profiled(str(tmp_path / "prof"),
                      lambda: rounds.append(sum(1 for _ in built)))
    snap = built.stats()
    built.close()
    assert rounds == [5]
    assert len([s for s in spans["pull/shard"] if "end" not in s]) == 5
    for name in ("shard.stack", "shard.put"):
        assert len(spans[name]) == 5, name
    # one parse per round, and the one that found every part done
    assert len(spans["shard.parse"]) == 6
    st = snap["stages"][0]
    assert st["rows"] == 640 and st["items"] == 5
    assert st["extra"]["assemble_s"] > 0


def test_nothing_built_while_no_profiler_collects(tmp_path, monkeypatch):
    built_names = []

    class Spy:
        def __init__(self, name, **kw):
            built_names.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

        def set_metadata(self, **kw):
            pass

    monkeypatch.setattr(obs_trace, "_TraceMe", Spy)
    assert not obs_trace._profiling()
    uri = _write_libsvm(tmp_path)
    built = _padded(uri).build()
    assert sum(1 for _ in built) == 8
    with obs_trace.span("obs.demo"):
        gc.collect()
    built.close()
    assert built_names == []
    # the same code under a collecting profiler does build them
    monkeypatch.setattr(obs_trace, "_profiling", lambda: True)
    sp = obs_trace.begin("x", "pipeline")
    assert sp.tm is not None and sp.end() >= 0
    assert built_names == ["x"]


def test_spans_close_when_the_work_raises(tmp_path, monkeypatch):
    opened, closed = [], []

    class Spy:
        def __init__(self, name, **kw):
            self.name = name

        def __enter__(self):
            opened.append(self.name)
            return self

        def __exit__(self, *exc):
            closed.append(self.name)

        def set_metadata(self, **kw):
            pass

    monkeypatch.setattr(obs_trace, "_TraceMe", Spy)
    monkeypatch.setattr(obs_trace, "_profiling", lambda: True)

    def fail(block):
        raise ValueError("stage failed")

    built = (Pipeline.from_uri(_write_libsvm(tmp_path))
             .parse(format="libsvm", engine="python").map(fail).build())
    rec = obs_trace.start()
    try:
        with pytest.raises(ValueError):
            for _ in built:
                pass
    finally:
        obs_trace.stop()
        built.close()
    assert opened and sorted(opened) == sorted(closed)
    names = [e[1] for e in rec.events() if e[0] == "X"]
    # the pull that raised ends the stream: recorded as the terminal one
    assert "pull/map" not in names and "pull/map.end" in names


def test_gc_collections_are_profiler_spans_only(tmp_path):
    rec = obs_trace.start()
    try:
        gc.collect()
    finally:
        obs_trace.stop()
    # a collection may start inside the recorder's locked sections, so
    # the recorder never sees one
    assert not [e for e in rec.events() if e[1] == "python.gc"]
    prof = _profiled(str(tmp_path / "prof"), gc.collect)
    assert {s["cat"] for s in prof["python.gc"]} == {"python"}
    assert 2 in {s["generation"] for s in prof["python.gc"]}


class _CollectingDict(dict):
    """A thread table that runs a collection inside the recorder's
    lock, as an allocation there can."""

    def keys(self):
        gc.collect()
        return super().keys()

    def __iter__(self):
        gc.collect()
        return super().__iter__()

    def setdefault(self, k, v=None):
        gc.collect()
        return super().setdefault(k, v)


@pytest.mark.parametrize("call", ["thread_names", "name_thread"])
def test_collection_inside_the_recorder_lock_returns(tmp_path, call):
    import jax
    rec = obs_trace.start()
    rec._threads = _CollectingDict(rec._threads)
    done = []

    def run():
        if call == "thread_names":
            rec.thread_names()
        else:
            rec.name_thread(7, "engine-7")
        done.append(True)

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        t = threading.Thread(target=run, daemon=True)
        t.start()
        t.join(timeout=30)
    finally:
        jax.profiler.stop_trace()
        obs_trace.stop()
    assert done == [True], f"{call} hung on a collection inside its lock"


class _DeviceOnly:
    """A device array whose host read raises: telemetry that reads it
    back fails the test."""

    def __init__(self, arr):
        self._arr = arr
        self.shape, self.dtype, self.nbytes = arr.shape, arr.dtype, arr.nbytes

    def __array__(self, *a, **kw):
        raise AssertionError("device array read back on the delivery path")

    def block_until_ready(self):
        self._arr.block_until_ready()
        return self


def test_to_device_delivery_reads_nothing_back(tmp_path, monkeypatch):
    import jax
    real_put = jax.device_put

    def put(x, *a, **kw):
        out = real_put(x, *a, **kw)
        return {k: _DeviceOnly(v) for k, v in out.items()}

    monkeypatch.setattr(jax, "device_put", put)
    uri = _write_libsvm(tmp_path)
    built = _padded(uri).build()
    got = list(built)
    snap = built.stats()
    built.close()
    assert len(got) == 8 and all(isinstance(b["num_rows"], _DeviceOnly) for b in got)
    dev = snap["stages"][-1]
    assert dev["kind"] == "to_device"
    assert dev["rows"] == 1000 == snap["stages"][0]["rows"]
    assert dev["nnz"] == snap["stages"][0]["nnz"] > 0


def test_shard_delivery_reads_nothing_back(tmp_path, monkeypatch):
    import jax
    from jax.sharding import Mesh

    from dmlc_tpu.parallel import sharded
    real = sharded.make_global_batch

    def make(local, mesh, axis="data"):
        return {k: _DeviceOnly(v) for k, v in real(local, mesh, axis).items()}

    monkeypatch.setattr(sharded, "make_global_batch", make)
    uri = _write_libsvm(tmp_path, rows=640)
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    built = (Pipeline.from_uri(uri).parse(format="libsvm", engine="python")
             .shard(mesh, row_bucket=128, nnz_bucket=1 << 11,
                    steady_replay=False).build())
    for _ in range(2):
        got = list(built)
        st = built.stats()["stages"][0]
        assert st["rows"] == 640 and st["items"] == len(got) == 3
    built.close()


def test_scheduled_delivery_bills_host_counts(tmp_path, monkeypatch):
    import jax

    from dmlc_tpu.pipeline import scheduler
    real_put = jax.device_put
    monkeypatch.setattr(jax, "device_put", lambda x, *a, **kw: {
        k: _DeviceOnly(v) for k, v in real_put(x, *a, **kw).items()})
    sched = scheduler.install()
    try:
        sched.register_tenant("t")
        built = _padded(_write_libsvm(tmp_path)).build(tenant="t")
        assert sum(1 for _ in built) == 8
        built.close()
        assert sched.to_dict()["tenants"]["t"]["rows"] == 1000
    finally:
        scheduler.uninstall()


@pytest.mark.parametrize("native", [False, True])
def test_shard_stage_reports_engine_and_stack_time(tmp_path, native):
    import jax
    from jax.sharding import Mesh
    uri = _write_libsvm(tmp_path, rows=900)
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    built = (Pipeline.from_uri(uri)
             .parse(format="libsvm", engine="native" if native else "python")
             .shard(mesh, row_bucket=128, nnz_bucket=1 << 11,
                    steady_replay=False).build())
    ex = built.run_epoch()["stages"][0]["extra"]
    one = [p.stats() for p in built._runners[0]._it._parsers
           if hasattr(p, "stats")]
    built.close()
    assert ex["assemble_s"] > 0
    if not native:
        assert "engine" not in ex
        return
    assert set(ex["engine"]) == set(one[0])
    assert ex["engine"]["chunks"] == sum(o["chunks"] for o in one)
    assert ex["engine"]["wall_ns"] == max(o["wall_ns"] for o in one)
