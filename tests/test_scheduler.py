"""Multi-tenant pipeline scheduler (dmlc_tpu.pipeline.scheduler):
DRR pull credits, admission control, backpressure/queue budgets,
per-tenant accounting + verdicts, the /tenants surface, and the
watchdog naming the starved tenant."""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from dmlc_tpu.obs import watchdog as obs_watchdog
from dmlc_tpu.obs.metrics import MetricsRegistry
from dmlc_tpu.pipeline import AdmissionError, Pipeline
from dmlc_tpu.pipeline import scheduler as sched_mod
from dmlc_tpu.pipeline.scheduler import (
    MANAGED_KNOBS, ENV_SCHED, PipelineScheduler,
)
from dmlc_tpu.utils.logging import DMLCError


@pytest.fixture(autouse=True)
def _clean_scheduler():
    yield
    sched_mod.uninstall()


def _mk(**kw):
    kw.setdefault("registry", MetricsRegistry())
    return PipelineScheduler(**kw)


def _libsvm_file(tmp_path, name="t.libsvm", rows=600):
    lines = [f"{i % 2} {i % 40 + 1}:1.5 {i % 70 + 3}:2.25\n"
             for i in range(rows)]
    p = tmp_path / name
    p.write_text("".join(lines))
    return str(p)


class TestDRR:
    def test_lone_tenant_unthrottled(self):
        s = _mk(quantum=2.0)
        s.register_tenant("a")
        for _ in range(50):
            s.acquire("a")
        row = s.to_dict()["tenants"]["a"]
        # a lone demander advances rounds itself: no credit waits
        assert row["credit_waits"] == 0
        assert s.rounds >= 25
        s.close()

    def test_weighted_interleave(self):
        """Two saturating tenants split pulls in weight proportion."""
        s = _mk(quantum=2.0, active_horizon_s=5.0, round_period_s=5.0)
        s.register_tenant("small", weight=1.0)
        s.register_tenant("big", weight=3.0)
        counts = {"small": 0, "big": 0}
        stop = time.monotonic() + 1.0

        def burn(name):
            while time.monotonic() < stop:
                s.acquire(name)
                counts[name] += 1

        ts = [threading.Thread(target=burn, args=(n,)) for n in counts]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        ratio = counts["big"] / max(counts["small"], 1)
        assert 2.0 <= ratio <= 4.5, (counts, ratio)
        s.close()

    def test_idle_tenant_keeps_burst_allowance(self):
        """An idle tenant's hoard caps at burst x quantum x weight —
        its next sparse burst clears instantly."""
        s = _mk(quantum=2.0, burst=2.0)
        s.register_tenant("idle", weight=3.0)
        for _ in range(40):
            s.acquire("idle")  # rounds advance, deficit replenishes
        with s._cond:
            assert s._tenants["idle"].deficit <= 2.0 * 2.0 * 3.0 + 1e-9
        s.close()

    def test_round_period_floor(self):
        """A peer holding unspent credits but not pulling cannot stall
        a broke tenant past round_period_s: the clocked round
        replenishes the demander."""
        s = _mk(quantum=1.0, burst=1.0, active_horizon_s=10.0,
                round_period_s=0.05)
        s.register_tenant("slow")
        s.register_tenant("fast")
        s.acquire("slow")   # slow now holds credit, stays "active"
        with s._cond:
            s._tenants["slow"].deficit = 5.0  # unspent hoard
        t0 = time.perf_counter()
        for _ in range(3):
            s.acquire("fast")
        # three clocked rounds at most: ~3 x round_period, never the
        # 10 s activity horizon
        assert time.perf_counter() - t0 < 1.0
        s.close()

    def test_cost_clamped_to_burst(self):
        s = _mk(quantum=1.0, burst=2.0)
        s.register_tenant("a")
        s.acquire("a", cost=1e9)  # clamped: must not deadlock
        s.close()

    def test_unknown_tenant_raises(self):
        s = _mk()
        with pytest.raises(DMLCError, match="unknown tenant"):
            s.acquire("ghost")
        s.close()

    def test_pause_blocks_resume_releases(self):
        s = _mk()
        s.register_tenant("a")
        s.pause("a")
        got = threading.Event()

        def puller():
            s.acquire("a")
            got.set()

        t = threading.Thread(target=puller, daemon=True)
        t.start()
        assert not got.wait(0.3)
        s.resume("a")
        assert got.wait(2.0)
        t.join()
        s.close()

    def test_close_releases_blocked_acquire(self):
        s = _mk()
        s.register_tenant("a")
        s.pause("a")
        done = threading.Event()

        def puller():
            s.acquire("a")
            done.set()

        t = threading.Thread(target=puller, daemon=True)
        t.start()
        time.sleep(0.1)
        s.close()
        assert done.wait(2.0)
        t.join()


class TestAdmission:
    def test_reject_past_budget(self):
        s = _mk()
        s.register_tenant("a", max_pipelines=1)
        mk = type("P", (), {"knobs": lambda self: []})
        p1 = mk()  # keep alive: admission slots are weakly held
        s.admit("a", p1)
        with pytest.raises(AdmissionError, match="pipeline budget"):
            s.admit("a", mk())
        row = s.to_dict()["tenants"]["a"]
        assert row["rejected"] == 1 and row["admitted"] == 1
        s.close()

    def test_queue_mode_waits_for_slot(self):
        s = _mk()
        s.register_tenant("a", max_pipelines=1, admission="queue")
        mk = type("P", (), {"knobs": lambda self: []})
        p1, p2 = mk(), mk()
        s.admit("a", p1)
        admitted = threading.Event()

        def second():
            s.admit("a", p2, timeout_s=5.0)
            admitted.set()

        t = threading.Thread(target=second, daemon=True)
        t.start()
        assert not admitted.wait(0.3)   # queued, not rejected
        s.release(p1)
        assert admitted.wait(3.0)
        t.join()
        assert s.to_dict()["tenants"]["a"]["queued"] == 1
        s.close()

    def test_queue_mode_times_out(self):
        s = _mk()
        s.register_tenant("a", max_pipelines=1, admission="queue")
        mk = type("P", (), {"knobs": lambda self: []})
        p1 = mk()
        s.admit("a", p1)
        with pytest.raises(AdmissionError, match="timed out"):
            s.admit("a", mk(), timeout_s=0.2)
        s.close()

    def test_gced_pipeline_frees_slot(self):
        s = _mk()
        s.register_tenant("a", max_pipelines=1)
        mk = type("P", (), {"knobs": lambda self: []})
        s.admit("a", mk())  # dropped immediately: weakref dies
        s.admit("a", mk())  # must not raise
        s.close()


class TestPipelineIntegration:
    def test_build_tenant_needs_scheduler(self, tmp_path):
        path = _libsvm_file(tmp_path)
        with pytest.raises(DMLCError, match="installed scheduler"):
            (Pipeline.from_uri(path).parse(format="libsvm")
             .batch(128).build(tenant="a"))

    def test_epoch_bills_the_tenant(self, tmp_path):
        path = _libsvm_file(tmp_path)
        s = sched_mod.install(quantum=8.0)
        s.register_tenant("job")
        built = (Pipeline.from_uri(path).parse(format="libsvm")
                 .batch(128).build(tenant="job"))
        n = sum(1 for _ in built)
        row = s.to_dict()["tenants"]["job"]
        assert row["pulls"] == n > 0
        assert row["bytes"] > 0 and row["rows"] == 600
        assert row["batches"] == n and row["batch_p99_s"] is not None
        # the snapshot carries the tenant label; the stored verdict
        # cites it (per-tenant bound verdicts, ANALYSIS_SCHEMA 4)
        assert built.stats()["tenant"] == "job"
        assert row["last_verdict"]["bound"] is not None
        v = s._tenants["job"].last_verdict
        assert v["tenant"] == "job"
        from dmlc_tpu.obs.analyze import VERDICT_KEYS
        assert sorted(v) == sorted(VERDICT_KEYS)
        built.close()
        assert s.to_dict()["tenants"]["job"]["pipelines"] == 0

    def test_queue_budget_rebalances_on_admission(self, tmp_path):
        """The scheduler owns the queue-capacity knobs: a second
        tenant's admission SHRINKS the first tenant's share."""
        path = _libsvm_file(tmp_path)
        s = sched_mod.install(queue_budget=32)
        s.register_tenant("a")
        s.register_tenant("b")
        pa = (Pipeline.from_uri(path)
              .parse(format="libsvm", engine="python")
              .batch(64).prefetch(depth="auto").build(tenant="a"))
        knob = next(k for k in pa.knobs()
                    if k.name == "prefetch.depth")
        assert knob.get() == 32  # whole budget: a is alone
        pb = (Pipeline.from_uri(path)
              .parse(format="libsvm", engine="python")
              .batch(64).prefetch(depth="auto").build(tenant="b"))
        assert knob.get() == 16  # b's admission halved a's share
        pb.close()
        assert knob.get() == 32  # and release restores it
        pa.close()

    def test_autotuner_excludes_scheduler_owned_knobs(self, tmp_path):
        path = _libsvm_file(tmp_path)
        s = sched_mod.install()
        s.register_tenant("a")
        built = (Pipeline.from_uri(path)
                 .parse(format="libsvm", engine="python")
                 .batch(64).prefetch(depth="auto")
                 .build(autotune=True, tenant="a"))
        assert built.scheduler_owned == MANAGED_KNOBS
        if built.autotuner is not None:
            names = {k.name for k in built.autotuner.knobs}
            assert not (names & set(MANAGED_KNOBS))
        built.close()

    def test_untenanted_build_untouched(self, tmp_path):
        """No tenant, no scheduler interplay — the pre-scheduler
        contract is unchanged even with one installed."""
        path = _libsvm_file(tmp_path)
        sched_mod.install().register_tenant("x")
        built = (Pipeline.from_uri(path).parse(format="libsvm")
                 .batch(128).build(autotune=True))
        assert built.tenant is None
        assert sum(1 for _ in built) > 0
        assert "tenant" not in built.stats()
        built.close()


class TestWatchdogNaming:
    def test_stall_report_names_the_tenant(self):
        """The acceptance detail: a wedged tenant is NAMED in the
        stall report (tenant/<name>.* wait), not inferred."""
        s = _mk()
        s.register_tenant("victim")
        s.pause("victim")
        wd = obs_watchdog.Watchdog(threshold_s=0.1, interval_s=0.05)
        wd.start()
        try:
            t = threading.Thread(target=s.acquire, args=("victim",),
                                 daemon=True)
            t.start()
            deadline = time.monotonic() + 5.0
            while not wd.reports and time.monotonic() < deadline:
                time.sleep(0.05)
            assert wd.reports, "watchdog never fired"
            names = [b["name"] for r in wd.reports
                     for b in r["blocked"]]
            assert any(n == "tenant/victim.paused" for n in names), \
                names
            detail = next(b["detail"] for r in wd.reports
                          for b in r["blocked"]
                          if b["name"].startswith("tenant/victim"))
            assert detail["tenant"] == "victim"
        finally:
            wd.stop()
            s.resume("victim")
            t.join(timeout=2)
            s.close()


class TestTenantsSurface:
    def test_endpoint_404_hint_without_scheduler(self):
        from dmlc_tpu.obs.serve import StatusServer
        srv = StatusServer(port=0)
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(srv.url("/tenants"), timeout=5)
            payload = json.load(ei.value)
            assert "DMLC_TPU_SCHED" in payload["hint"]
        finally:
            srv.close()

    def test_endpoint_serves_rows(self, tmp_path):
        from dmlc_tpu.obs.serve import StatusServer
        path = _libsvm_file(tmp_path)
        s = sched_mod.install()
        s.register_tenant("svc", weight=2.0)
        built = (Pipeline.from_uri(path).parse(format="libsvm")
                 .batch(128).build(tenant="svc"))
        for _ in built:
            pass
        srv = StatusServer(port=0)
        try:
            with urllib.request.urlopen(srv.url("/tenants"),
                                        timeout=5) as r:
                doc = json.load(r)
            assert doc["schema"] == sched_mod.TENANTS_SCHEMA
            row = doc["tenants"]["svc"]
            assert row["pulls"] > 0 and row["weight"] == 2.0
            assert row["last_verdict"]["bound"]
        finally:
            srv.close()
            built.close()

    def test_obsctl_renders_fabricated_view(self):
        """Pin the obsctl tenants rendering against a fabricated
        /tenants payload (the gang/control fabricated-view pattern)."""
        import importlib.util
        import os
        spec = importlib.util.spec_from_file_location(
            "obsctl", os.path.join(os.path.dirname(__file__), "..",
                                   "scripts", "obsctl.py"))
        obsctl = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(obsctl)
        doc = {
            "schema": 1, "quantum": 4.0, "burst": 2.0,
            "queue_budget": 48, "rounds": 17,
            "tenants": {
                "svc": {"weight": 2.0, "deficit": 3.5, "paused": False,
                        "pipelines": 1, "max_pipelines": 4,
                        "queue_share": 32, "pulls": 120,
                        "batch_p50_s": 0.002, "batch_p99_s": 0.011,
                        "queue_occupancy": 0.4,
                        "admitted": 1, "rejected": 0, "queued": 0,
                        "last_verdict": {"verdict_id": "v3-abc",
                                         "bound": "parse",
                                         "band": "plateau",
                                         "confidence": "high"},
                        "watermark": {"uri": "feed.log", "windows": 9,
                                      "watermark_records": 900,
                                      "watermark_bytes": 12345,
                                      "last_advance_s_ago": 0.2,
                                      "retries": 1}},
                "batch": {"weight": 1.0, "deficit": 0.0, "paused": True,
                          "pipelines": 0, "max_pipelines": 2,
                          "queue_share": None, "pulls": 8,
                          "batch_p50_s": None, "batch_p99_s": None,
                          "queue_occupancy": None,
                          "admitted": 2, "rejected": 1, "queued": 1},
            },
        }
        out = obsctl.render_tenants(doc)
        assert "svc" in out and "parse/high" in out
        assert "11.0" in out            # p99 ms
        assert "watermark 900 records" in out
        assert "PAUSED" in out
        assert "1 rejected" in out

    def test_install_if_env(self, monkeypatch):
        monkeypatch.setenv(ENV_SCHED, "quantum=3,queue=9,burst=4")
        s = sched_mod.install_if_env()
        assert s is not None
        assert s.quantum == 3.0 and s.queue_budget == 9 \
            and s.burst == 4.0
        sched_mod.uninstall()
        monkeypatch.setenv(ENV_SCHED, "0")
        assert sched_mod.install_if_env() is None

    def test_scheduler_metrics_collector(self):
        reg = MetricsRegistry()
        s = _mk(registry=reg)
        s.register_tenant("a")
        s.acquire("a")
        snap = reg.snapshot()
        sched = snap["collectors"]["scheduler"]
        assert sched["tenants"]["a"]["pulls"] == 0  # acquire != pull
        assert sched["rounds"] >= 1
        s.close()
        assert "scheduler" not in reg.snapshot()["collectors"]


class TestAnalyzeTenant:
    def test_attribute_passes_tenant_through(self):
        from dmlc_tpu.obs import analyze
        snap = {"schema": 1, "epoch": 2, "wall_s": 1.0, "tenant": "t9",
                "stages": [{"name": "parse", "kind": "parse",
                            "items": 10, "rows": 100, "nnz": 0,
                            "bytes": 10 ** 9, "wait_s": 0.9}]}
        v = analyze.attribute(snap)
        assert v["tenant"] == "t9" and v["bound"] == "parse"
        v2 = analyze.attribute({**snap, "tenant": None})
        assert v2["tenant"] is None
        # the tenant participates in the verdict identity
        assert v["verdict_id"] != v2["verdict_id"]


class TestContention:
    def test_noisy_tenants_throttled_victim_stream_unchanged(
            self, tmp_path, monkeypatch):
        """Three tenants share one process under one scheduler: a
        padded-parse saturator, an ``obj://`` saturator reading through
        the object-store emulator, and a sparse victim. Under
        contention the saturators block on credits, every tenant is
        billed on its ``/tenants`` row, and the victim reads the same
        stream it reads alone."""
        import hashlib

        import dmlc_tpu.io.objstore as objstore
        import dmlc_tpu.io.pagestore as ps
        monkeypatch.setattr(ps, "default_store_dir",
                            lambda: str(tmp_path / "pagestore"))
        victim_src = _libsvm_file(tmp_path, "victim.libsvm", rows=600)
        noisy_src = _libsvm_file(tmp_path, "noisy.libsvm", rows=20000)
        wire_src = _libsvm_file(tmp_path, "wire.libsvm", rows=4000)
        em = objstore.configure(root=str(tmp_path / "objroot"),
                                latency_s=0.001)
        stop = threading.Event()
        errors = []
        threads = []
        built = []
        try:
            em.put_file("b", "feed.libsvm", wire_src)
            s = sched_mod.install(_mk(quantum=2.0, burst=2.0,
                                      queue_budget=24))
            s.register_tenant("victim", weight=16.0, max_pipelines=2)
            s.register_tenant("parse_heavy", weight=1.0)
            s.register_tenant("wire_heavy", weight=1.0)
            victim = (Pipeline.from_uri(victim_src)
                      .parse(format="libsvm", nthreads=1)
                      .batch(64).build(tenant="victim"))
            noisy = (Pipeline.from_uri(noisy_src)
                     .parse(format="libsvm", nthreads=1)
                     .batch(128, pad=True, nnz_bucket=128 * 2)
                     .build(tenant="parse_heavy"))
            wire = (Pipeline.from_uri("obj://b/feed.libsvm")
                    .parse(format="libsvm")
                    .batch(256).build(tenant="wire_heavy"))
            built = [victim, noisy, wire]

            def stream_hash():
                h = hashlib.sha256()
                for b in victim:
                    h.update(b.content_hash().encode())
                return h.hexdigest()

            def loop(name, pipe):
                try:
                    while not stop.is_set():
                        for _ in pipe:
                            if stop.is_set():
                                break
                except Exception as e:  # noqa: BLE001
                    errors.append(f"{name}: {e!r}")

            alone = stream_hash()
            threads = [threading.Thread(target=loop, args=(n, p),
                                        daemon=True)
                       for n, p in (("parse_heavy", noisy),
                                    ("wire_heavy", wire))]
            for t in threads:
                t.start()

            def throttled():
                rows = s.to_dict()["tenants"]
                return (rows["parse_heavy"]["credit_waits"]
                        + rows["wire_heavy"]["credit_waits"])

            contended = []
            deadline = time.monotonic() + 60
            while not contended or throttled() == 0:
                assert time.monotonic() < deadline, \
                    "no saturator ever blocked on credits"
                contended.append(stream_hash())
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=60)
            alive = [t for t in threads if t.is_alive()]
            if not alive:
                for p in built:
                    p.close()
            objstore.configure(None)
        assert not alive, "saturator threads failed to stop"
        assert not errors, errors
        assert set(contended) == {alone}, \
            "victim stream changed under contention"
        rows = s.to_dict()["tenants"]
        assert set(rows) == {"victim", "parse_heavy", "wire_heavy"}
        assert all(r["pulls"] > 0 for r in rows.values())
        assert (rows["parse_heavy"]["credit_waits"]
                + rows["wire_heavy"]["credit_waits"]) > 0
