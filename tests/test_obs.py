"""dmlc_tpu.obs: trace recorder + Chrome export golden keys, probe-vs-
span consistency, metrics registry schema, stall watchdog diagnosis,
rate-limited log channel, gang merging, and the tracing-overhead smoke
gate (tier-1: a tiny traced pipeline must stay within 5% of untraced).
"""

import json
import os
import threading
import time

import pytest

from dmlc_tpu.obs import log as obs_log
from dmlc_tpu.obs import metrics as obs_metrics
from dmlc_tpu.obs import trace as obs_trace
from dmlc_tpu.obs.export import (
    chrome_events, merge_chrome_files, write_chrome,
)
from dmlc_tpu.obs.metrics import REGISTRY, merge_snapshots
from dmlc_tpu.obs.watchdog import Watchdog
from dmlc_tpu.data.threaded_iter import ThreadedIter

CHROME_REQUIRED_KEYS = ("ph", "ts", "pid", "tid", "name")


@pytest.fixture(autouse=True)
def _obs_clean():
    """Every test starts with tracing off and fresh log dedup state."""
    obs_trace.stop()
    obs_log.reset()
    yield
    obs_trace.stop()
    obs_log.reset()


def _write_libsvm(tmp_path, rows=600, name="obs.libsvm"):
    lines = [f"{i % 2} 1:0.5 7:1.25 9:{i}.0" for i in range(rows)]
    p = tmp_path / name
    p.write_text("\n".join(lines) + "\n")
    return str(p)


class TestTraceRecorder:
    def test_span_instant_counter_recorded(self):
        rec = obs_trace.start(capacity=100)
        with obs_trace.span("work", "test", {"k": 1}):
            pass
        obs_trace.instant("marker", "test")
        obs_trace.counter("queue", {"depth": 3, "skip": "notnum"})
        assert obs_trace.stop() is rec
        phs = [e[0] for e in rec.events()]
        assert phs == ["X", "i", "C"]
        # counter keeps numeric series only
        assert rec.events()[2][6] == {"depth": 3}

    def test_off_is_noop(self):
        assert obs_trace.active() is None
        with obs_trace.span("ghost"):
            pass
        obs_trace.instant("ghost")
        obs_trace.counter("ghost", {"x": 1})  # nothing raises, no state

    def test_ring_buffer_drops_oldest(self):
        rec = obs_trace.start(capacity=10)
        for i in range(25):
            obs_trace.instant(f"e{i}")
        obs_trace.stop()
        assert rec.recorded == 25
        assert rec.dropped == 15
        names = [e[1] for e in rec.events()]
        assert names == [f"e{i}" for i in range(15, 25)]

    def test_start_over_live_recorder_warns(self):
        from dmlc_tpu.utils.logging import set_log_sink
        hits = []
        set_log_sink(lambda lvl, msg: hits.append((lvl, msg)))
        try:
            obs_trace.start()
            obs_trace.instant("doomed")
            obs_trace.start()  # replaces: the buffered event is gone
            obs_trace.stop()
        finally:
            set_log_sink(None)
        assert any("replacing an active recorder" in m
                   for _, m in hits), hits

    def test_thread_names_tracked(self):
        rec = obs_trace.start()

        def work():
            obs_trace.instant("from-thread")

        t = threading.Thread(target=work, name="obs-test-thread")
        t.start()
        t.join()
        obs_trace.stop()
        assert "obs-test-thread" in rec.thread_names().values()


class TestChromeExport:
    def test_golden_required_keys(self, tmp_path):
        """Golden: every exported event carries the Chrome trace-event
        required keys; X events carry dur; the envelope is loadable."""
        rec = obs_trace.start()
        with obs_trace.span("stage", "pipeline"):
            time.sleep(0.001)
        obs_trace.instant("tick")
        obs_trace.counter("engine", {"busy_ns": 10})
        obs_trace.stop()
        path = str(tmp_path / "trace.json")
        write_chrome(rec, path)
        doc = json.load(open(path))
        assert "traceEvents" in doc and doc["traceEvents"]
        for ev in doc["traceEvents"]:
            for key in CHROME_REQUIRED_KEYS:
                assert key in ev, (key, ev)
        xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert xs and all("dur" in e for e in xs)
        assert any(e["ph"] == "C" for e in doc["traceEvents"])
        # metadata names the process and the recording threads
        metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
        assert any(e["name"] == "process_name" for e in metas)
        assert any(e["name"] == "thread_name" for e in metas)

    def test_merge_tags_processes(self, tmp_path):
        a = obs_trace.TraceRecorder()
        a.complete("wa", time.perf_counter(), 0.001)
        b = obs_trace.TraceRecorder()
        b.complete("wb", time.perf_counter(), 0.001)
        pa, pb = str(tmp_path / "a.json"), str(tmp_path / "b.json")
        write_chrome(a, pa, pid=1001, process_name="rank 0")
        write_chrome(b, pb, pid=1002, process_name="rank 1")
        merged = merge_chrome_files([pa, pb],
                                    str(tmp_path / "gang.json"))
        pids = {e["pid"] for e in merged["traceEvents"]}
        assert pids == {1001, 1002}
        assert os.path.exists(tmp_path / "gang.json")

    def test_rank_tag_from_env(self, monkeypatch):
        monkeypatch.setenv("DMLC_TPU_TASK_ID", "3")
        rec = obs_trace.TraceRecorder()
        rec.instant("x")
        evs = chrome_events(rec)
        proc = [e for e in evs if e["ph"] == "M"
                and e["name"] == "process_name"][0]
        assert "rank 3" in proc["args"]["name"]


class TestPipelineTracing:
    def test_span_count_matches_probe_items(self, tmp_path):
        """Probe-vs-span consistency: with tracing on, every stage
        emits exactly probe.items ``pull/<stage>`` spans, and span
        totals agree with the probe's wait_s (same perf_counter pair,
        so agreement is construction, checked to 10%)."""
        from dmlc_tpu.pipeline import Pipeline
        uri = _write_libsvm(tmp_path)
        built = (Pipeline.from_uri(uri)
                 .parse(format="libsvm", engine="python",
                        chunk_size=2048)
                 .batch(64)
                 .prefetch(depth=2)
                 .build())
        path = str(tmp_path / "pipe.json")
        with built.trace(path):
            for _ in built:
                pass
        snap = built.stats()
        built.close()
        evs = json.load(open(path))["traceEvents"]
        for st in snap["stages"]:
            spans = [e for e in evs
                     if e["ph"] == "X" and e["name"] == f"pull/{st['name']}"]
            assert len(spans) == st["items"], st["name"]
            total_s = sum(e["dur"] for e in spans) / 1e6
            # the terminal end-of-stream wait is a separate span
            ends = [e for e in evs if e["ph"] == "X"
                    and e["name"] == f"pull/{st['name']}.end"]
            total_s += sum(e["dur"] for e in ends) / 1e6
            assert total_s == pytest.approx(st["wait_s"],
                                            rel=0.10, abs=0.002)

    def test_queue_wait_spans_present(self, tmp_path):
        """ThreadedIter waits appear as queue-category spans under the
        names the docs promise."""
        rec = obs_trace.start()
        ti = ThreadedIter(max_capacity=1, name="obs.demo")

        src = iter(range(8))
        ti.init(lambda: next(src, None))
        time.sleep(0.05)  # producer fills the 1-slot queue and blocks
        while ti.next() is not None:
            time.sleep(0.005)  # slow consumer: producer re-blocks
        ti.destroy()
        obs_trace.stop()
        names = {e[1] for e in rec.events()}
        assert "obs.demo.producer_wait" in names

    def test_overhead_smoke_under_5pct(self, tmp_path):
        """Tier-1 gate: tracing a small pipeline costs <5% wall time
        vs tracing off. One shared pipeline, traced and untraced
        epochs INTERLEAVED (off,on × 5), judged on the QUIETEST
        adjacent pair — climate is shared inside a pair on this
        burstable host, where min-vs-min across rounds flaked on 2x
        wall swings (the PR-10 profiler gate's statistic, applied to
        this gate for the same reason); plus a small absolute slack
        for scheduler noise on sub-100ms epochs."""
        from dmlc_tpu.pipeline import Pipeline
        uri = _write_libsvm(tmp_path, rows=4000, name="overhead.libsvm")
        built = (Pipeline.from_uri(uri)
                 .parse(format="libsvm", engine="python",
                        chunk_size=4096)
                 .batch(256)
                 .build())

        def epoch_wall():
            t0 = time.perf_counter()
            for _ in built:
                pass
            return time.perf_counter() - t0

        epoch_wall()  # warm caches/imports outside the measurement
        off, on = [], []
        recorded = 0
        for _ in range(5):
            off.append(epoch_wall())
            obs_trace.start()
            try:
                on.append(epoch_wall())
            finally:
                recorded += obs_trace.stop().recorded
        built.close()
        assert recorded > 0  # tracing was actually on
        grace = 0.010 / min(off)  # flat 10 ms, scaled to the wall
        ratios = [a / b for a, b in zip(on, off)]
        assert min(ratios) <= 1.05 + grace, (on, off, ratios)


class TestMetricsRegistry:
    def test_snapshot_schema(self):
        """The versioned snapshot shape (schema 1) is pinned: bump
        METRICS_SCHEMA when changing it."""
        reg = obs_metrics.MetricsRegistry()
        reg.counter("events").inc(3)
        reg.gauge("tier").set("pages")
        reg.histogram("wait_s").observe(0.25)
        snap = reg.snapshot()
        assert snap["schema"] == obs_metrics.METRICS_SCHEMA == 1
        for key in ("schema", "pid", "rank", "counters", "gauges",
                    "histograms", "collectors"):
            assert key in snap, key
        assert snap["counters"]["events"] == 3
        assert snap["gauges"]["tier"] == "pages"
        h = snap["histograms"]["wait_s"]
        assert h["count"] == 1 and h["min"] == h["max"] == 0.25
        assert sum(h["buckets"].values()) == 1
        json.dumps(snap)  # plain JSON end to end

    def test_collector_registration_and_weak_drop(self):
        reg = obs_metrics.MetricsRegistry()

        class Surface:
            def stats(self):
                return {"produced": 7}

        s = Surface()
        name = reg.register("queue/x", s, Surface.stats)
        assert reg.snapshot()["collectors"][name] == {"produced": 7}
        del s  # weakly held: the surface drops out on its own
        import gc
        gc.collect()
        assert name not in reg.snapshot()["collectors"]

    def test_collector_name_collision_suffixed(self):
        reg = obs_metrics.MetricsRegistry()

        class Surface:
            def stats(self):
                return {}

        a, b = Surface(), Surface()
        na = reg.register("queue/q", a, Surface.stats)
        nb = reg.register("queue/q", b, Surface.stats)
        assert na != nb and na == "queue/q"

    def test_collector_exception_reports_none(self):
        reg = obs_metrics.MetricsRegistry()

        class Broken:
            def stats(self):
                raise RuntimeError("torn down")

        b = Broken()
        name = reg.register("broken", b, Broken.stats)
        assert reg.snapshot()["collectors"][name] is None

    def test_existing_surfaces_register(self):
        """The pre-obs stats() surfaces land in one snapshot: a named
        ThreadedIter and a thread's BufferPool here (native engine +
        pipeline covered by their own suites)."""
        from dmlc_tpu.utils.memory import thread_local_pool
        ti = ThreadedIter(max_capacity=2, name="reg.demo")
        src = iter([1, 2])
        ti.init(lambda: next(src, None))
        while ti.next() is not None:
            pass
        pools = []  # held: collectors are registered weakly
        t = threading.Thread(target=lambda: pools.append(thread_local_pool()),
                             name="reg.pool")
        t.start()
        t.join(timeout=5)
        assert not t.is_alive() and pools
        snap = REGISTRY.snapshot()
        keys = [k for k in snap["collectors"] if k.startswith("queue/reg.demo")]
        assert keys, snap["collectors"].keys()
        got = snap["collectors"][keys[0]]
        assert got["produced"] == 2 and "capacity" in got
        assert snap["collectors"]["buffer_pool/reg.pool"] == {
            "allocated": 0, "reused": 0}
        ti.destroy()
        assert not [k for k in REGISTRY.snapshot()["collectors"]
                    if k.startswith("queue/reg.demo")]

    def test_merge_snapshots_keys_by_rank(self):
        a = {"schema": 1, "pid": 10, "rank": 0, "counters": {}}
        b = {"schema": 1, "pid": 11, "rank": 1, "counters": {}}
        c = {"schema": 1, "pid": 12, "rank": None, "counters": {}}
        merged = merge_snapshots([a, b, c])
        assert set(merged["workers"]) == {"rank0", "rank1", "pid12"}


class TestWatchdog:
    def test_stall_produces_diagnosis_report(self, tmp_path):
        """Acceptance: a deliberate stall yields ONE report naming the
        blocked stage and its queue state, with metrics + stacks."""
        release = threading.Event()

        def blocked_next():
            release.wait(30.0)  # deliberate wedge
            return None

        ti = ThreadedIter(max_capacity=2, name="stalled.stage")
        ti.init(blocked_next)
        report_path = str(tmp_path / "stall.json")
        wd = Watchdog(threshold_s=0.15, interval_s=0.05,
                      report_path=report_path)
        consumer = threading.Thread(target=ti.next, daemon=True)
        with wd:
            consumer.start()
            deadline = time.time() + 5.0
            while not wd.reports and time.time() < deadline:
                time.sleep(0.02)
        release.set()
        consumer.join(timeout=5.0)
        ti.destroy()
        assert wd.reports, "watchdog never fired"
        report = wd.reports[0]
        blocked = report["blocked"]
        names = [b["name"] for b in blocked]
        assert "stalled.stage.consumer_wait" in names, names
        entry = [b for b in blocked
                 if b["name"] == "stalled.stage.consumer_wait"][0]
        assert entry["blocked_s"] >= 0.15
        # queue state rides in the report
        assert entry["detail"]["qsize"] == 0
        assert entry["detail"]["capacity"] == 2
        # metrics snapshot + all-thread stacks
        assert report["metrics"]["schema"] == obs_metrics.METRICS_SCHEMA
        assert "Thread" in report["stacks"]
        # and the JSON report file landed
        on_disk = json.load(open(report_path))
        assert on_disk["kind"] == "dmlc_tpu_stall_report"
        assert on_disk["blocked"][0]["name"] == entry["name"]

    def test_stage_exception_leaves_no_phantom_wait(self, tmp_path):
        """A raising stage must unregister its watchdog wait: the
        token leak would later fire a stall report for a pull that
        ended (in an exception) long ago."""
        from dmlc_tpu.pipeline import Pipeline
        from dmlc_tpu.utils.logging import DMLCError
        uri = _write_libsvm(tmp_path, rows=300, name="boom.libsvm")

        def boom(item):
            raise DMLCError("deliberate stage failure")

        built = (Pipeline.from_uri(uri)
                 .parse(format="libsvm", engine="python")
                 .map(boom)
                 .build())
        with Watchdog(threshold_s=0.1, interval_s=0.03) as wd:
            with pytest.raises(DMLCError):
                for _ in built:
                    pass
            time.sleep(0.3)  # several polls past the threshold
            assert wd.reports == [], wd.reports
        built.close()

    def test_no_report_below_threshold(self):
        ti = ThreadedIter(max_capacity=2, name="quick.stage")
        src = iter(range(5))
        ti.init(lambda: next(src, None))
        with Watchdog(threshold_s=5.0, interval_s=0.05) as wd:
            while ti.next() is not None:
                pass
            time.sleep(0.2)
        ti.destroy()
        assert wd.reports == []

    def test_replacing_watchdog_inherits_blocked_waits(self):
        """A successor watchdog must see a pull that was ALREADY
        blocked when it took over (blocked waits never re-register, so
        neither start()'s predecessor-stop nor a late stop() may clear
        the shared registry); the predecessor's poll thread is stopped
        so stalls are not double-reported."""
        release = threading.Event()
        ti = ThreadedIter(max_capacity=2, name="handover")
        ti.init(lambda: (release.wait(30.0), None)[1])
        a = Watchdog(threshold_s=0.15, interval_s=0.04).start()
        consumer = threading.Thread(target=ti.next, daemon=True)
        consumer.start()
        time.sleep(0.05)          # the wait registers under A
        b = Watchdog(threshold_s=0.15, interval_s=0.04).start()
        a.stop()                  # late stop must not blind B
        deadline = time.time() + 5.0
        while not b.reports and time.time() < deadline:
            time.sleep(0.02)
        b.stop()
        release.set()
        consumer.join(timeout=5.0)
        ti.destroy()
        assert a.reports == []    # predecessor was stopped, not racing
        assert b.reports, "successor never saw the inherited stall"
        assert [x["name"] for x in b.reports[0]["blocked"]] \
            == ["handover.consumer_wait"]

    def test_one_report_per_stall(self, tmp_path):
        release = threading.Event()

        def blocked_next():
            release.wait(30.0)
            return None

        ti = ThreadedIter(max_capacity=2)
        ti.init(blocked_next)
        wd = Watchdog(threshold_s=0.1, interval_s=0.03)
        consumer = threading.Thread(target=ti.next, daemon=True)
        with wd:
            consumer.start()
            deadline = time.time() + 5.0
            while not wd.reports and time.time() < deadline:
                time.sleep(0.02)
            time.sleep(0.3)  # several more polls over the SAME stall
            n = len(wd.reports)
        release.set()
        consumer.join(timeout=5.0)
        ti.destroy()
        assert n == 1


class TestObsLog:
    def _capture(self):
        from dmlc_tpu.utils.logging import set_log_sink
        hits = []
        set_log_sink(lambda lvl, msg: hits.append((lvl, msg)))
        return hits

    def _restore(self):
        from dmlc_tpu.utils.logging import set_log_sink
        set_log_sink(None)

    def test_warn_once_dedups(self):
        hits = self._capture()
        try:
            assert obs_log.warn_once("k1", "first") is True
            assert obs_log.warn_once("k1", "second") is False
            assert obs_log.warn_once("k2", "other") is True
            assert [m for _, m in hits] == ["first", "other"]
        finally:
            self._restore()

    def test_warn_limited_rate(self):
        hits = self._capture()
        try:
            assert obs_log.warn_limited("r", "a", min_interval_s=60)
            assert not obs_log.warn_limited("r", "b", min_interval_s=60)
            assert obs_log.warn_limited("r", "c", min_interval_s=0.0)
            assert len(hits) == 2
        finally:
            self._restore()

    def test_nonzero_rank_suppressed(self, monkeypatch):
        monkeypatch.setenv("DMLC_TPU_TASK_ID", "2")
        hits = self._capture()
        try:
            before = REGISTRY.counter("log.suppressed.rank").value
            assert obs_log.warn_once("gang-key", "dup") is False
            assert hits == []
            assert REGISTRY.counter("log.suppressed.rank").value \
                == before + 1
            # rank-local facts opt out of the gang dedup
            assert obs_log.warn_once("local-key", "mine",
                                     all_ranks=True) is True
            assert [m for _, m in hits] == ["mine"]
        finally:
            self._restore()


class TestGangTracing:
    def test_trace_if_env_and_merge(self, tmp_path, monkeypatch):
        d = str(tmp_path / "gang")
        monkeypatch.setenv("DMLC_TPU_TRACE_DIR", d)
        monkeypatch.setenv("DMLC_TPU_TASK_ID", "0")
        with obs_trace.trace_if_env():
            with obs_trace.span("worker-work"):
                pass
        assert os.path.exists(os.path.join(d, "trace-rank0.json"))
        from dmlc_tpu.parallel.launch import merge_gang_traces
        out = merge_gang_traces(d)
        assert out is not None
        merged = json.load(open(out))
        assert any(e.get("name") == "worker-work"
                   for e in merged["traceEvents"])

    def test_trace_if_env_noop_without_env(self, monkeypatch):
        monkeypatch.delenv("DMLC_TPU_TRACE_DIR", raising=False)
        with obs_trace.trace_if_env():
            assert obs_trace.active() is None

    def test_merge_gang_traces_empty_dir(self, tmp_path):
        from dmlc_tpu.parallel.launch import merge_gang_traces
        assert merge_gang_traces(str(tmp_path)) is None
