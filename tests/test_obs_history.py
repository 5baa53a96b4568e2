"""Analysis half of the obs plane (PR 8): time-series history, gang
aggregation, and bottleneck attribution.

Covers: the bounded downsampling ring (coarsening keeps the byte
budget AND the run's span; sampling costs <2% of a pipeline epoch —
the tightened overhead smoke gate), the /history endpoint, histogram
p50/p99 estimates, watchdog reports carrying the decay INTO a stall,
crash bundles gaining history.json (a REAL subprocess crash leaves >=2
samples spanning the run), the gang aggregator (in-process rollups +
explicit gaps, and the acceptance gang: a REAL 2-process launch_local
gang serving /history and /gang live where one rank dies mid-poll and
the aggregator keeps serving with an explicit gap), the attribution
engine's verdicts against synthetic and real snapshots, band-aware
BENCH comparison over the repo's own BENCH_r0*.json archive, and the
scripts/obsctl.py CLI.
"""

import glob
import json
import os
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

from dmlc_tpu.obs import aggregate as obs_agg
from dmlc_tpu.obs import analyze as obs_analyze
from dmlc_tpu.obs import flight as obs_flight
from dmlc_tpu.obs import log as obs_log
from dmlc_tpu.obs import timeseries as obs_ts
from dmlc_tpu.obs import trace as obs_trace
from dmlc_tpu.obs import watchdog as obs_watchdog
from dmlc_tpu.obs.metrics import REGISTRY, MetricsRegistry
from dmlc_tpu.obs.serve import StatusServer
from dmlc_tpu.obs.watchdog import Watchdog

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "scripts"))
import obsctl  # noqa: E402


@pytest.fixture(autouse=True)
def _obs_clean():
    """No flight recorder, history ring, aggregator, or trace state
    leaks across tests."""
    obs_flight.uninstall()
    obs_ts.uninstall()
    obs_agg.uninstall()
    obs_trace.stop()
    obs_trace.clear_fallback()
    obs_log.reset()
    yield
    obs_flight.uninstall()
    obs_ts.uninstall()
    obs_agg.uninstall()
    obs_trace.stop()
    obs_trace.clear_fallback()
    obs_log.reset()


def _get(url: str, timeout_s: float = 5.0):
    with urllib.request.urlopen(url, timeout=timeout_s) as resp:
        return resp.status, resp.read()


def _write_libsvm(tmp_path, rows=600, name="hist.libsvm"):
    lines = [f"{i % 2} 1:0.5 7:1.25 9:{i}.0" for i in range(rows)]
    p = tmp_path / name
    p.write_text("\n".join(lines) + "\n")
    return str(p)


class TestTimeSeriesRing:
    def test_sampler_thread_collects(self):
        reg = MetricsRegistry()
        reg.counter("ticks").inc()
        ring = obs_ts.TimeSeriesRing(period_s=0.02, registry=reg)
        ring.start()
        try:
            deadline = time.time() + 5.0
            while len(ring.samples()) < 3 and time.time() < deadline:
                reg.counter("ticks").inc()
                time.sleep(0.01)
        finally:
            ring.stop()
        samples = ring.samples()
        assert len(samples) >= 3
        # monotonic time, numeric-only leaves, counters present
        ts = [s["t"] for s in samples]
        assert ts == sorted(ts)
        assert all(isinstance(v, (int, float))
                   for s in samples for v in s["v"].values())
        assert samples[-1]["v"]["counters.ticks"] >= \
            samples[0]["v"]["counters.ticks"]

    def test_numeric_leaves_sections(self):
        reg = MetricsRegistry()
        reg.counter("rows").inc(7)
        reg.gauge("depth").set(3)
        reg.gauge("tier").set("pages")  # string: no timeline
        reg.histogram("wait_s").observe(0.25)

        class Surface:
            def stats(self):
                return {"qsize": 2, "note": "text", "nested": {"n": 5}}

        s = Surface()
        reg.register("queue/demo", s, Surface.stats)
        leaves = obs_ts.numeric_leaves(reg.snapshot())
        assert leaves["counters.rows"] == 7
        assert leaves["gauges.depth"] == 3
        assert "gauges.tier" not in leaves
        assert leaves["histograms.wait_s.count"] == 1
        assert "histograms.wait_s.p50" in leaves
        assert leaves["collectors.queue/demo.qsize"] == 2
        assert leaves["collectors.queue/demo.nested.n"] == 5
        assert "collectors.queue/demo.note" not in leaves

    def test_coarsening_holds_budget_and_span(self):
        """The byte-budget soak: thousands of appends never exceed the
        budget, the oldest sample (the span anchor) survives every
        coarsening pass, and resolution degrades instead of history
        disappearing."""
        ring = obs_ts.TimeSeriesRing(period_s=1.0, budget_bytes=4 << 10)
        for i in range(20000):
            ring.append(float(i), {"counters.rows": i,
                                   "gauges.queue.depth": i % 7,
                                   "histograms.wait_s.sum": i * 0.1})
            assert ring.approx_bytes() <= ring.budget_bytes
        d = ring.to_dict()
        assert d["samples"][0]["t"] == 0.0          # span anchor
        assert d["samples"][-1]["t"] > 19000.0      # newest kept
        assert d["stride"] > 1 and d["coarsenings"] >= 1
        assert d["kept"] == len(d["samples"])
        assert d["kept"] < 20000                    # actually bounded
        # samples stay evenly ordered after repeated halving
        ts = [s["t"] for s in d["samples"]]
        assert ts == sorted(ts)

    def test_forced_sample_bypasses_stride(self):
        """Crash/stall dumps force a final sample: once the ring has
        coarsened (stride >= 2), a plain append may be skipped but a
        forced one must always be stored — the black box carries the
        actual end state, not one up to stride*period_s stale."""
        ring = obs_ts.TimeSeriesRing(period_s=1.0, budget_bytes=4 << 10)
        for i in range(20000):
            ring.append(float(i), {"counters.rows": i,
                                   "gauges.queue.depth": i % 7,
                                   "histograms.wait_s.sum": i * 0.1})
        assert ring.to_dict()["stride"] >= 2
        # consecutive ticks cannot both be keep-ticks at stride >= 2:
        # without force at least one of these would be dropped
        assert ring.append(99998.0, {"counters.rows": 1}, force=True)
        assert ring.append(99999.0, {"counters.rows": 2}, force=True)
        assert ring.to_dict()["samples"][-1]["t"] == 99999.0

    def test_install_if_env(self, monkeypatch):
        monkeypatch.delenv(obs_ts.ENV_HISTORY_S, raising=False)
        assert obs_ts.install_if_env() is None
        monkeypatch.setenv(obs_ts.ENV_HISTORY_S, "0.05")
        monkeypatch.setenv(obs_ts.ENV_HISTORY_BYTES, str(32 << 10))
        ring = obs_ts.install_if_env()
        assert ring is not None and obs_ts.active() is ring
        assert ring.period_s == 0.05
        assert ring.budget_bytes == 32 << 10
        # idempotent: a second hook call returns the SAME ring
        assert obs_ts.install_if_env() is ring
        obs_ts.uninstall()
        assert obs_ts.active() is None

    def test_history_endpoint(self):
        # installed but NOT started: samples driven manually so the
        # endpoint's counts are deterministic
        ring = obs_ts.TimeSeriesRing(period_s=60)
        obs_ts._ring = ring
        REGISTRY.counter("hist.demo").inc(5)
        ring.sample_now(t=time.time() - 100.0)
        ring.sample_now()
        with StatusServer() as srv:
            status, body = _get(srv.url("/history"))
            doc = json.loads(body)
            assert doc["schema"] == obs_ts.TIMESERIES_SCHEMA
            assert doc["kept"] == 2
            assert doc["samples"][-1]["v"]["counters.hist.demo"] == 5
            # ?seconds=N trims to the trailing window
            doc = json.loads(_get(srv.url("/history?seconds=30"))[1])
            assert len(doc["samples"]) == 1
        obs_ts.uninstall()

    def test_history_endpoint_404_without_ring(self):
        with StatusServer() as srv:
            with pytest.raises(urllib.error.HTTPError) as e:
                _get(srv.url("/history"))
            assert e.value.code == 404

    def test_overhead_smoke_under_2pct(self, tmp_path):
        """Tier-1 gate (the ISSUE-8 acceptance number): sampling
        enabled costs <2% of a pipeline epoch. Interleaved rounds,
        judged on the QUIETEST adjacent (off, on) pair — climate is
        shared inside a pair on this burstable box, where min-vs-min
        across all rounds flaked on 2x wall swings (the PR-10
        profiler gate's statistic, applied here for the same
        reason)."""
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
        sampled = 0
        for _ in range(5):
            off.append(epoch_wall())
            ring = obs_ts.install(period_s=0.05)
            try:
                on.append(epoch_wall())
            finally:
                sampled += len(ring.samples())
                obs_ts.uninstall()
        built.close()
        assert sampled > 0  # sampling was actually on
        grace = 0.010 / min(off)  # flat 10 ms, scaled to the wall
        ratios = [a / b for a, b in zip(on, off)]
        assert min(ratios) <= 1.02 + grace, (on, off, ratios)


class TestHistogramQuantiles:
    def test_estimates_ordered_and_clamped(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat")
        for v in (0.001, 0.002, 0.004, 0.008, 0.5):
            h.observe(v)
        s = h.summary()
        assert s["min"] <= s["p50"] <= s["p99"] <= s["max"]
        # the single outlier pulls p99 toward max, not p50
        assert s["p50"] < 0.1 and s["p99"] > 0.1

    def test_empty_histogram_has_no_estimates(self):
        s = MetricsRegistry().histogram("empty").summary()
        assert s["p50"] is None and s["p99"] is None

    def test_single_observation_pins_both(self):
        reg = MetricsRegistry()
        reg.histogram("one").observe(0.125)
        s = reg.histogram("one").summary()
        assert s["p50"] == s["p99"] == 0.125  # clamped to min==max


class TestWatchdogHistory:
    def test_stall_report_attaches_decay(self):
        """A stall report carries the trailing time-series samples —
        the decay INTO the stall, not just the frozen end state."""
        ring = obs_ts.TimeSeriesRing(period_s=60)
        obs_ts._ring = ring  # installed, manually driven
        REGISTRY.counter("decay.rows").inc(100)
        ring.sample_now(t=time.time() - 10.0)
        REGISTRY.counter("decay.rows").inc(5)  # the rate died
        wd = Watchdog(threshold_s=0.02, interval_s=999,
                      history_s=60.0).start()
        try:
            token = obs_watchdog.begin_wait("pull/dying.demo")
            time.sleep(0.03)
            report = wd.check()
            obs_watchdog.end_wait(token)
        finally:
            wd.stop()
        assert report is not None
        assert report["history_s"] == 60.0
        hist = report["history"]
        assert len(hist) >= 2  # the old sample + the forced fresh one
        assert hist[0]["v"]["counters.decay.rows"] == 100
        assert hist[-1]["v"]["counters.decay.rows"] == 105

    def test_report_without_ring_has_empty_history(self):
        assert obs_ts.active() is None
        wd = Watchdog(threshold_s=0.02, interval_s=999).start()
        try:
            token = obs_watchdog.begin_wait("pull/lonely.demo")
            time.sleep(0.03)
            report = wd.check()
            obs_watchdog.end_wait(token)
        finally:
            wd.stop()
        assert report is not None and report["history"] == []


class TestFlightHistory:
    def test_flight_owns_ring_when_none_installed(self, tmp_path):
        assert obs_ts.active() is None
        fl = obs_flight.FlightRecorder(
            out_dir=str(tmp_path / "fl"),
            metrics_interval_s=0.05).install()
        try:
            ring = obs_ts.active()
            assert ring is not None
            assert ring.period_s == 0.05
        finally:
            fl.uninstall()
        assert obs_ts.active() is None  # owned ring removed with it

    def test_flight_shares_preinstalled_ring(self, tmp_path):
        ring = obs_ts.install(period_s=30)
        fl = obs_flight.FlightRecorder(
            out_dir=str(tmp_path / "fl")).install()
        try:
            assert obs_ts.active() is ring  # joined, not displaced
        finally:
            fl.uninstall()
        assert obs_ts.active() is ring      # not owned: survives
        obs_ts.uninstall()

    def test_bundle_gains_history_json(self, tmp_path):
        fl = obs_flight.FlightRecorder(
            out_dir=str(tmp_path / "fl"),
            metrics_interval_s=0.05).install()
        try:
            REGISTRY.counter("flight.hist").inc(9)
            time.sleep(0.12)
            d = fl.dump("unit_test")
            hist = json.load(open(os.path.join(d, "history.json")))
            assert hist["schema"] == obs_ts.TIMESERIES_SCHEMA
            assert len(hist["samples"]) >= 2
            assert hist["samples"][-1]["v"]["counters.flight.hist"] == 9
            # metrics.json's history mirrors the SAME ring's samples
            metrics = json.load(open(os.path.join(d, "metrics.json")))
            assert len(metrics["history"]) == len(hist["samples"])
        finally:
            fl.uninstall()

    def test_subprocess_crash_bundle_history_spans_run(self, tmp_path):
        """Satellite regression pin: a REAL worker crash leaves a
        bundle whose history.json holds >=2 samples SPANNING the run —
        the shared ring replaced flight's private sampler end to end
        (env wiring included)."""
        from dmlc_tpu.parallel.launch import launch_local
        from dmlc_tpu.utils.logging import DMLCError
        out = str(tmp_path / "flight")
        script = tmp_path / "crash.py"
        script.write_text(
            "import time\n"
            "from dmlc_tpu.obs.timeseries import install_if_env\n"
            "ring = install_if_env()\n"
            "assert ring is not None, 'history env missing'\n"
            "from dmlc_tpu.obs.flight import install_if_env as fl_env\n"
            "assert fl_env() is not None\n"
            "from dmlc_tpu.obs.metrics import REGISTRY\n"
            "for i in range(6):\n"
            "    REGISTRY.counter('doomed.ticks').inc()\n"
            "    time.sleep(0.05)\n"
            "raise RuntimeError('deliberate history crash')\n"
        )
        env = {"PYTHONPATH": os.pathsep.join(
            [REPO] + os.environ.get("PYTHONPATH", "").split(os.pathsep))}
        with pytest.raises(DMLCError):
            launch_local(1, [sys.executable, str(script)], env=env,
                         flight_dir=out, history_s=0.05, timeout=120)
        bundles = glob.glob(os.path.join(out, "flight-*"))
        assert len(bundles) == 1, bundles
        hist = json.load(open(os.path.join(bundles[0], "history.json")))
        samples = hist["samples"]
        assert len(samples) >= 2, hist
        assert samples[-1]["t"] - samples[0]["t"] >= 0.05
        # the run's counters are on the timeline, rising
        assert samples[-1]["v"]["counters.doomed.ticks"] == 6


class TestGangAggregator:
    def _server(self, name, count):
        reg = MetricsRegistry()
        reg.counter("agg.rows").inc(count)
        reg.gauge("agg.depth").set(count // 100)
        return StatusServer(registry=reg)

    def test_rollups_and_labels(self):
        a = self._server("a", 100)
        b = self._server("b", 200)
        try:
            agg = obs_agg.GangAggregator(ports=[a.port, b.port],
                                         period_s=60)
            status = agg.poll_once()
            assert status == {f"port{a.port}": True,
                              f"port{b.port}": True}
            agg.poll_once()
            view = agg.view()
            assert view["schema"] == obs_agg.GANG_SCHEMA
            assert set(view["ranks"]) == {f"port{a.port}",
                                          f"port{b.port}"}
            ra = view["ranks"][f"port{a.port}"]
            assert ra["unreachable"] is False and ra["polls_ok"] == 2
            assert ra["series"]["samples"][-1]["v"][
                "counters.agg.rows"] == 100
            roll = view["rollup"]["samples"][-1]["v"]
            assert roll["gang.expected"] == 2.0
            assert roll["gang.reachable"] == 2.0
            assert roll["sum.counters.agg.rows"] == 300
            assert roll["min.counters.agg.rows"] == 100
            assert roll["max.counters.agg.rows"] == 200
        finally:
            a.close()
            b.close()

    def test_unreachable_rank_gets_explicit_gap(self):
        """The dead member's series STOPS (no interpolation) and the
        poll logs an explicit gap while the aggregator keeps serving
        the survivor."""
        a = self._server("a", 100)
        b = self._server("b", 200)
        agg = obs_agg.GangAggregator(ports=[a.port, b.port],
                                     period_s=60, timeout_s=0.5)
        agg.poll_once()
        b_label = f"port{b.port}"
        b.close()  # the rank "dies mid-poll"
        try:
            status = agg.poll_once()
            assert status[f"port{a.port}"] is True
            assert status[b_label] is False
            view = agg.view()
            dead = view["ranks"][b_label]
            assert dead["unreachable"] is True
            assert dead["last_error"]
            assert len(dead["gaps"]) == 1
            assert dead["gaps"][0]["first"] is True
            # series: exactly the one pre-death sample, nothing invented
            assert len(dead["series"]["samples"]) == 1
            roll = view["rollup"]["samples"][-1]["v"]
            assert roll["gang.reachable"] == 1.0
            assert roll["sum.counters.agg.rows"] == 100
        finally:
            a.close()
            agg.stop()

    def test_install_if_env(self, monkeypatch):
        monkeypatch.delenv(obs_agg.ENV_GANG_POLL_S, raising=False)
        assert obs_agg.install_if_env() is None
        srv = self._server("a", 7)
        try:
            monkeypatch.setenv(obs_agg.ENV_GANG_POLL_S, "0.05")
            monkeypatch.setenv("DMLC_TPU_SERVE_PORTS", str(srv.port))
            agg = obs_agg.install_if_env()
            assert agg is not None and agg.ports == [srv.port]
            deadline = time.time() + 5.0
            while agg.view()["polls"] < 2 and time.time() < deadline:
                time.sleep(0.02)
            assert agg.view()["polls"] >= 2
        finally:
            obs_agg.uninstall()
            srv.close()

    def test_gang_endpoint_404_without_aggregator(self):
        with StatusServer() as srv:
            with pytest.raises(urllib.error.HTTPError) as e:
                _get(srv.url("/gang"))
            assert e.value.code == 404


class TestGangServeLive:
    """ISSUE-8 acceptance: a REAL 2-process launch_local gang serves
    /history and /gang live DURING the run; one rank dying mid-poll
    leaves the rank-0 aggregator serving, with the dead rank's series
    showing an explicit gap and /gang marking it unreachable (extends
    the PR-4 scrape-under-load pattern)."""

    def test_two_process_gang_history_and_gap(self, tmp_path):
        from dmlc_tpu.parallel.launch import find_free_ports, launch_local
        script = tmp_path / "gang_worker.py"
        stop_file = tmp_path / "stop"
        die_file = tmp_path / "die"
        script.write_text(
            "import os, sys, time\n"
            "from dmlc_tpu.obs.serve import serve_if_env\n"
            "from dmlc_tpu.obs.timeseries import install_if_env as h\n"
            "from dmlc_tpu.obs.aggregate import install_if_env as g\n"
            "from dmlc_tpu.obs.metrics import REGISTRY\n"
            "srv = serve_if_env()\n"
            "assert srv is not None, 'serve port env missing'\n"
            "assert h() is not None, 'history env missing'\n"
            "rank = int(os.environ['DMLC_TPU_TASK_ID'])\n"
            "agg = g()\n"
            "assert (agg is not None) == (rank == 0), (rank, agg)\n"
            "REGISTRY.counter('gang.rows').inc(100 * (rank + 1))\n"
            "deadline = time.time() + 60\n"
            "while time.time() < deadline:\n"
            "    REGISTRY.counter('gang.ticks').inc()\n"
            "    if rank == 1 and os.path.exists(sys.argv[2]):\n"
            "        os._exit(0)\n"  # vanish mid-poll
            "    if rank == 0 and os.path.exists(sys.argv[1]):\n"
            "        break\n"
            "    time.sleep(0.05)\n"
        )
        ports = find_free_ports(2)
        env = {"PYTHONPATH": os.pathsep.join(
            [REPO] + os.environ.get("PYTHONPATH", "").split(os.pathsep))}
        result = {}

        def gang():
            try:
                result["codes"] = launch_local(
                    2, [sys.executable, str(script), str(stop_file),
                        str(die_file)],
                    env=env, serve_ports=ports, history_s=0.1,
                    gang_poll_s=0.1, timeout=90)
            except Exception as e:  # noqa: BLE001
                result["error"] = e

        t = threading.Thread(target=gang, daemon=True)
        t.start()
        try:
            # phase 1: both ranks aggregated live — rank 0's /gang
            # shows two reachable members with samples
            deadline = time.time() + 45.0
            view = None
            while time.time() < deadline:
                try:
                    view = json.loads(_get(
                        f"http://127.0.0.1:{ports[0]}/gang",
                        timeout_s=2.0)[1])
                except (OSError, urllib.error.URLError, ValueError):
                    time.sleep(0.05)
                    continue
                ranks = view.get("ranks") or {}
                if (set(ranks) == {"rank0", "rank1"}
                        and all(r["series"]["samples"]
                                for r in ranks.values())):
                    break
                time.sleep(0.05)
            assert view is not None and set(view["ranks"]) == \
                {"rank0", "rank1"}, f"gang never aggregated: {result}"
            r1 = view["ranks"]["rank1"]
            assert r1["unreachable"] is False
            assert r1["series"]["samples"][-1]["v"][
                "counters.gang.rows"] == 200
            # /history is live on BOTH ranks during the run
            for port in ports:
                h = json.loads(_get(
                    f"http://127.0.0.1:{port}/history")[1])
                assert h["samples"], f"no history on :{port}"
            # phase 2: rank 1 dies mid-poll; the aggregator keeps
            # serving with an explicit gap and marks it unreachable
            die_file.write_text("die")
            deadline = time.time() + 45.0
            dead = None
            while time.time() < deadline:
                try:
                    view = json.loads(_get(
                        f"http://127.0.0.1:{ports[0]}/gang",
                        timeout_s=2.0)[1])
                except (OSError, urllib.error.URLError, ValueError):
                    time.sleep(0.05)
                    continue
                dead = view["ranks"]["rank1"]
                if dead["unreachable"] and dead["gaps"]:
                    break
                time.sleep(0.05)
            assert dead is not None and dead["unreachable"] is True, \
                f"rank1 never marked unreachable: {result}"
            assert dead["gaps"][0]["error"]
            assert dead["polls_failed"] >= 1
            # the survivor's series keeps growing past the death
            alive = view["ranks"]["rank0"]
            assert alive["unreachable"] is False
            roll = view["rollup"]["samples"][-1]["v"]
            assert roll["gang.reachable"] == 1.0
            assert roll["gang.expected"] == 2.0
        finally:
            stop_file.write_text("stop")
            t.join(timeout=45.0)
        assert result.get("codes") == [0, 0], result


def _snap(stages, wall_s=2.0):
    return {"schema": 1, "epoch": 1, "wall_s": wall_s,
            "stages": stages, "knobs": {}}


class TestAnalyze:
    def test_parse_bound(self):
        v = obs_analyze.attribute(_snap([
            {"name": "parse", "kind": "parse", "wait_s": 1.4,
             "bytes": 1 << 30},
            {"name": "to_device", "kind": "to_device", "wait_s": 0.2,
             "extra": {"xfer_wait_s": 0.2}},
        ]), epoch_gauges=[2.0, 2.2])
        assert v["bound"] == "parse" and v["confidence"] == "high"
        assert v["band"] == "elevated"
        assert sorted(v) == sorted(obs_analyze.VERDICT_KEYS)
        assert any("parse wait 1.4" in e for e in v["evidence"])
        json.dumps(v)  # plain JSON end to end

    def test_xfer_bound(self):
        v = obs_analyze.attribute(_snap([
            {"name": "parse", "kind": "parse", "wait_s": 0.3,
             "bytes": 1 << 30},
            {"name": "to_device", "kind": "to_device", "wait_s": 1.5,
             "extra": {"xfer_wait_s": 1.5}},
        ]))
        assert v["bound"] == "xfer"

    def test_assemble_bound_fused_first_stage(self):
        # the ABI-5 fused rung: ONE assemble-kind stage carrying the
        # engine's measured assemble seconds — parse is its delivery
        # wait minus those
        v = obs_analyze.attribute(_snap([
            {"name": "assemble", "kind": "assemble", "wait_s": 1.0,
             "bytes": 1 << 30,
             "extra": {"assembly_path": "native-padded",
                       "assemble_s": 0.8, "engine": {}}},
        ]))
        assert v["stage_waits"]["parse_s"] == pytest.approx(0.2)
        assert v["stage_waits"]["assemble_s"] == pytest.approx(0.8)
        assert v["bound"] == "assemble"
        assert any("assembly_path=native-padded" in e
                   for e in v["evidence"])

    def test_fused_carveout_uses_stage0_assemble_only(self):
        """The fused-parse carve-out subtracts only stage 0's OWN
        measured assemble seconds — downstream staging assembly
        belongs to other stages and must not eat the parse credit."""
        v = obs_analyze.attribute(_snap([
            {"name": "assemble", "kind": "assemble", "wait_s": 2.0,
             "bytes": 1 << 30,
             "extra": {"assembly_path": "native-padded",
                       "assemble_s": 0.3}},
            {"name": "to_device", "kind": "to_device", "wait_s": 0.1,
             "extra": {"staging_assemble_s": 1.0,
                       "xfer_wait_s": 0.1}},
        ]))
        assert v["stage_waits"]["parse_s"] == pytest.approx(1.7)
        assert v["stage_waits"]["assemble_s"] == pytest.approx(1.3)
        assert v["bound"] == "parse"

    def test_cache_first_stage_not_credited_to_parse(self):
        """Only the fused ASSEMBLE-kind first stage earns the parse
        credit: a cache- or shard-first pipeline's stage-0 wait is
        replay/shard I/O — a 'parse'-bound verdict for an epoch that
        never parsed would be fabricated evidence."""
        v = obs_analyze.attribute(_snap([
            {"name": "cache", "kind": "cache", "wait_s": 1.4,
             "bytes": 1 << 30},
            {"name": "to_device", "kind": "to_device", "wait_s": 0.1,
             "extra": {"xfer_wait_s": 0.1}},
        ]))
        assert v["stage_waits"]["parse_s"] == 0.0
        assert v["bound"] != "parse"
        assert not any("parse wait" in e for e in v["evidence"])

    def test_credit_limited_overrides_waits(self):
        v = obs_analyze.attribute(_snap([
            {"name": "parse", "kind": "parse", "wait_s": 1.4,
             "bytes": 1 << 30},
        ]), epoch_gauges=[0.2, 0.4, 0.3])
        assert v["bound"] == "credit-limited"
        assert v["band"] == "drained"

    def test_consumer_bound_when_waits_tiny(self):
        v = obs_analyze.attribute(_snap([
            {"name": "parse", "kind": "parse", "wait_s": 0.02,
             "bytes": 1 << 30},
        ], wall_s=5.0))
        assert v["bound"] == "consumer"

    def test_wire_bound(self):
        metrics = {"counters": {"objstore.get": 50,
                                "objstore.bytes": 1 << 30,
                                "pagestore.hit": 1,
                                "pagestore.miss": 40}}
        v = obs_analyze.attribute(_snap([
            {"name": "parse", "kind": "parse", "wait_s": 1.4,
             "bytes": 1 << 30},
        ]), metrics=metrics)
        assert v["bound"] == "wire"
        assert any("objstore" in e for e in v["evidence"])

    def test_sharded_vs_unsharded_legs_differ_in_evidence(self):
        """Acceptance: a fused and a sharded leg may share a bound but
        must NOT share evidence — the verdict names the measured
        waits, which differ."""
        fused = obs_analyze.attribute(_snap([
            {"name": "assemble", "kind": "assemble", "wait_s": 1.2,
             "bytes": 1 << 30,
             "extra": {"assembly_path": "native-padded",
                       "assemble_s": 0.3}},
        ]))
        sharded = obs_analyze.attribute(_snap([
            {"name": "parse", "kind": "parse", "wait_s": 0.7,
             "bytes": 1 << 30},
            {"name": "assemble", "kind": "assemble", "wait_s": 0.9,
             "bytes": 1 << 30,
             "extra": {"assembly_path": "python-fused",
                       "assemble_s": 0.2}},
        ]))
        assert fused["evidence"] != sharded["evidence"]
        assert fused["stage_waits"] != sharded["stage_waits"]
        assert any("native-padded" in e for e in fused["evidence"])
        assert any("python-fused" in e for e in sharded["evidence"])

    def test_compare_in_band_variance_not_flagged(self):
        a = {"metric": "m", "value": 1.0, "epochs": 10,
             "run_band": "plateau", "parse_cpu_gbps_core": 1.0,
             "gauge_bands": {"plateau": {"epochs": 10,
                                         "sustained": 1.0}}}
        b = json.loads(json.dumps(a))
        b["gauge_bands"]["plateau"]["sustained"] = 0.9  # -10%: in-band
        r = obs_analyze.compare(a, b)
        assert r["bands"]["plateau"]["status"] == "in-band"
        assert r["regressions"] == []
        b["gauge_bands"]["plateau"]["sustained"] = 0.5  # -50%: real
        r = obs_analyze.compare(a, b)
        assert r["bands"]["plateau"]["status"] == "regression"
        assert len(r["regressions"]) == 1

    def test_compare_cross_band_is_incomparable(self):
        a = {"metric": "m", "value": 1.0,
             "gauge_bands": {"drained": {"epochs": 8,
                                         "sustained": 0.2}}}
        b = {"metric": "m", "value": 1.1,
             "gauge_bands": {"full": {"epochs": 8, "sustained": 1.1}}}
        r = obs_analyze.compare(a, b)
        assert all(row["status"] == "incomparable"
                   for row in r["bands"].values())
        assert r["regressions"] == []

    def test_compare_archive_files(self):
        """The repo's own BENCH_r0*.json archive (campaign wrappers):
        compare loads them, reports band-aware rows, and flags no
        regression across differing credit climates."""
        a = os.path.join(REPO, "BENCH_r04.json")
        b = os.path.join(REPO, "BENCH_r05.json")
        r = obs_analyze.compare_files(a, b)
        assert r["bands"], r
        assert r["regressions"] == []
        # credit-immune CPU rate compared despite the band mismatch
        assert r["parse_cpu"]["status"] == "in-band"
        # identical runs never regress
        r2 = obs_analyze.compare_files(b, b)
        assert r2["regressions"] == [] and r2["improvements"] == []

    def test_diagnose_bench_prefers_embedded_analysis(self, tmp_path):
        verdict = obs_analyze.attribute(_snap([
            {"name": "parse", "kind": "parse", "wait_s": 1.0,
             "bytes": 1 << 20}]))
        doc = {"metric": "m", "value": 1.0, "analysis": verdict}
        p = tmp_path / "bench.json"
        p.write_text(json.dumps(doc))
        assert obs_analyze.diagnose_bench(str(p)) == verdict

    def test_analyze_endpoint_serves_pipeline_verdict(self, tmp_path):
        from dmlc_tpu.pipeline import Pipeline
        uri = _write_libsvm(tmp_path, rows=2000)
        built = (Pipeline.from_uri(uri)
                 .parse(format="libsvm", engine="python",
                        chunk_size=2048)
                 .batch(128)
                 .build())
        built.run_epoch()
        with StatusServer() as srv:
            v = json.loads(_get(srv.url("/analyze"))[1])
        built.close()
        assert v["bound"] in obs_analyze.BOUNDS
        assert sorted(v) == sorted(obs_analyze.VERDICT_KEYS)
        assert v["stage_waits"]["stages"]

    def test_analyze_endpoint_scopes_wire_counters_to_epoch(self):
        """/analyze deltas the wire counters against the previous
        epoch's close: cold-hydration traffic from EARLIER work must
        not flip a purely local later epoch to wire-bound."""
        reg = MetricsRegistry()
        state = {"epoch": 1}

        class Holder:
            def stats(self):
                return _snap([{"name": "parse", "kind": "parse",
                               "wait_s": 1.4, "bytes": 1 << 30}])\
                    | {"epoch": state["epoch"]}

        h = Holder()
        reg.register("pipeline", h, Holder.stats)
        reg.counter("objstore.get").inc(50)
        reg.counter("objstore.bytes").inc(1 << 30)
        reg.counter("pagestore.miss").inc(40)
        reg.counter("pagestore.hit").inc(1)
        with StatusServer(registry=reg) as srv:
            # first call: no baseline yet — cumulative counters still
            # look like wire traffic
            v1 = json.loads(_get(srv.url("/analyze"))[1])
            assert v1["bound"] == "wire"
            state["epoch"] = 2   # a LOCAL epoch, no new wire traffic
            v2 = json.loads(_get(srv.url("/analyze"))[1])
            assert v2["bound"] != "wire"
            assert not any("objstore" in e for e in v2["evidence"])

    def test_attribute_real_epoch_with_profiler(self, tmp_path):
        """attribute() on a real CompiledPipeline.stats() epoch and a
        private sampling profiler's document: the verdict is
        schema-valid, carries evidence, per-stage waits and weighted
        hot frames, and never names a bound whose measured wait is
        zero."""
        from dmlc_tpu.obs import profile as obs_profile
        from dmlc_tpu.pipeline import Pipeline
        uri = _write_libsvm(tmp_path, rows=6000)
        built = (Pipeline.from_uri(uri)
                 .parse(format="libsvm", engine="auto", chunk_size=8192)
                 .batch(256, pad=True, nnz_bucket=256 * 3)
                 .build())
        prof = obs_profile.StackProfiler(hz=211)
        try:
            prof.start()
            snap = built.run_epoch()
            prof.sample_now(force=True)  # even a sub-period epoch samples
            doc = prof.to_dict()
        finally:
            prof.stop()
            built.close()
        v = obs_analyze.attribute(snap, profile_doc=doc)
        assert sorted(v) == sorted(obs_analyze.VERDICT_KEYS)
        assert v["bound"] in obs_analyze.BOUNDS
        assert v["evidence"]
        assert v["stage_waits"]["stages"]
        assert v["hot_frames"], "no hot_frames from the sampling profiler"
        for hf in v["hot_frames"]:
            assert sorted(hf) == ["frac", "frame", "samples"]
            assert hf["samples"] > 0 and 0.0 <= hf["frac"] <= 1.0
        key = {"parse": "parse_s", "assemble": "assemble_s",
               "xfer": "xfer_s"}.get(v["bound"])
        if key is not None:
            assert v["stage_waits"][key] > 0


class TestObsctl:
    def test_compare_cli_in_band(self, tmp_path, capsys):
        a = {"metric": "m", "value": 1.0, "run_band": "plateau",
             "gauge_bands": {"plateau": {"epochs": 6,
                                         "sustained": 1.0}}}
        b = json.loads(json.dumps(a))
        b["gauge_bands"]["plateau"]["sustained"] = 0.93
        pa, pb = tmp_path / "a.json", tmp_path / "b.json"
        pa.write_text(json.dumps(a))
        pb.write_text(json.dumps(b))
        rc = obsctl.main(["compare", str(pa), str(pb)])
        out = capsys.readouterr().out
        assert rc == 0
        assert "in-band" in out and "no regressions" in out

    def test_compare_cli_regression_exit_code(self, tmp_path, capsys):
        a = {"metric": "m", "value": 1.0,
             "gauge_bands": {"plateau": {"epochs": 6,
                                         "sustained": 1.0}}}
        b = {"metric": "m", "value": 0.5,
             "gauge_bands": {"plateau": {"epochs": 6,
                                         "sustained": 0.5}}}
        pa, pb = tmp_path / "a.json", tmp_path / "b.json"
        pa.write_text(json.dumps(a))
        pb.write_text(json.dumps(b))
        rc = obsctl.main(["compare", str(pa), str(pb)])
        assert rc == 3
        assert "REGRESSION" in capsys.readouterr().out

    def test_top_once(self, capsys):
        reg = MetricsRegistry()

        class Holder:
            def stats(self):
                return _snap([
                    {"name": "parse", "kind": "parse", "items": 12,
                     "rows": 3000, "nnz": 9000, "bytes": 1 << 20,
                     "wait_s": 0.5, "wait_frac": 0.25,
                     "throughput_gbps": 0.8, "rows_per_s": 1500.0,
                     "queue_depth_mean": 2.0, "queue_cap": 4,
                     "queue_occupancy": 0.5},
                ])

        h = Holder()
        reg.register("pipeline", h, Holder.stats)
        with StatusServer(registry=reg) as srv:
            rc = obsctl.main(["top", "--once", "--port",
                              str(srv.port)])
        out = capsys.readouterr().out
        assert rc == 0
        assert "parse" in out and "wait_s" in out and "2.0/4" in out

    def test_diagnose_live_endpoint(self, tmp_path, capsys):
        from dmlc_tpu.pipeline import Pipeline
        uri = _write_libsvm(tmp_path, rows=2000)
        built = (Pipeline.from_uri(uri)
                 .parse(format="libsvm", engine="python",
                        chunk_size=2048)
                 .batch(128)
                 .build())
        built.run_epoch()
        with StatusServer() as srv:
            rc = obsctl.main(["diagnose", "--port", str(srv.port)])
        built.close()
        out = capsys.readouterr().out
        assert rc == 0 and "bound:" in out and "evidence:" in out

    def test_history_cli_surfaces_404_payload(self, capsys):
        """The server's 404s carry a JSON {error, hint} body; the CLI
        must surface it (exit 2) instead of dying on the bare
        urllib HTTPError before ever reading the payload."""
        with StatusServer() as srv:
            rc = obsctl.main(["history", "--port", str(srv.port)])
        out = capsys.readouterr().out
        assert rc == 2
        assert "no timeseries ring installed" in out
        assert "DMLC_TPU_HISTORY_S" in out   # the hint survives

    def test_history_and_gang_cli(self, capsys):
        ring = obs_ts.install(period_s=60)
        REGISTRY.counter("cli.demo").inc(3)
        ring.sample_now()
        with StatusServer() as srv:
            obs_agg.install(ports=[srv.port], period_s=60)
            obs_agg.active().poll_once()
            rc_h = obsctl.main(["history", "--port", str(srv.port)])
            rc_g = obsctl.main(["gang", "--port", str(srv.port)])
        out = capsys.readouterr().out
        assert rc_h == 0 and "samples spanning" in out
        assert rc_g == 0 and "gang of 1" in out and "up" in out
