"""Chaos suite for dmlc_tpu.resilience (ISSUE 5).

Pins the three pillars: RetryPolicy semantics (deterministic backoff,
classifier, shared budget, per-attempt timeout), the seeded fault-
injection plane (same seed => same faults; retry-until-success at
every instrumented seam), and elastic gang supervision (a REAL
2-process launch_local gang survives an injected mid-epoch worker
crash with byte-identical epoch output vs. the fault-free run, the
restart visible on /metrics and the merged gang trace; budget
exhausted = prompt teardown with a flight bundle, not a hang).
"""

import hashlib
import json
import os
import sys
import time

import numpy as np
import pytest

from dmlc_tpu.data.rowblock import RowBlock
from dmlc_tpu.resilience import (
    CRASH_EXIT, AttemptTimeout, FaultPlan, RestartPolicy, RetryBudget,
    RetryPolicy, guarded, inject, policy_for, reset_policies,
    retry_counts, set_policy,
)
from dmlc_tpu.utils.logging import DMLCError

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _noop_sleep(_s):
    pass


@pytest.fixture(autouse=True)
def _clean_plane():
    """Every test leaves the process chaos-free and policy-default."""
    yield
    inject.uninstall()
    reset_policies()


def _gang_env(extra=None):
    env = {"JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.pathsep.join(
               [_REPO] + [p for p in
                          os.environ.get("PYTHONPATH", "").split(
                              os.pathsep) if p])}
    if extra:
        env.update(extra)
    return env


# ---------------------------------------------------------------- policy

class TestRetryPolicy:
    def test_retries_then_succeeds(self):
        calls = []
        pol = RetryPolicy(max_attempts=4, sleep=_noop_sleep)

        def fn():
            calls.append(1)
            if len(calls) < 3:
                raise IOError("transient")
            return "ok"

        assert pol.call("t.basic", fn) == "ok"
        assert len(calls) == 3
        assert retry_counts()["t.basic"] == 2

    def test_backoff_schedule_is_deterministic(self):
        slept_a, slept_b = [], []
        for slept in (slept_a, slept_b):
            calls = [0]
            pol = RetryPolicy(max_attempts=4, base_delay_s=0.1,
                              multiplier=2.0, jitter=0.2,
                              sleep=slept.append)

            def fn():
                calls[0] += 1
                if calls[0] < 4:
                    raise IOError("x")
                return calls[0]

            pol.call("t.backoff", fn)
        assert slept_a == slept_b  # jitter is seeded, not random
        assert slept_a == [pol.delay_for("t.backoff", a)
                           for a in (1, 2, 3)]
        # exponential shape survives the +-20% jitter
        assert slept_a[0] < slept_a[1] < slept_a[2]

    def test_non_retryable_raises_immediately(self):
        pol = RetryPolicy(sleep=_noop_sleep)
        calls = []

        def bad_value():
            calls.append(1)
            raise ValueError("parse error")

        with pytest.raises(ValueError):
            pol.call("t.cls", bad_value)
        assert len(calls) == 1

        def missing():
            calls.append(1)
            raise FileNotFoundError("gone")

        calls.clear()
        with pytest.raises(FileNotFoundError):
            pol.call("t.cls", missing)
        assert len(calls) == 1  # permanent OSError subclasses: no retry

    def test_attempts_exhausted_reraises_last(self):
        pol = RetryPolicy(max_attempts=3, sleep=_noop_sleep)
        calls = []

        def fn():
            calls.append(1)
            raise IOError(f"fail {len(calls)}")

        with pytest.raises(IOError, match="fail 3"):
            pol.call("t.exhaust", fn)
        assert len(calls) == 3

    def test_budget_shared_across_sites(self):
        budget = RetryBudget(1)
        pol = RetryPolicy(max_attempts=5, budget=budget,
                          sleep=_noop_sleep)
        a_calls, b_calls = [], []

        def flaky(calls, ok_after):
            calls.append(1)
            if len(calls) < ok_after:
                raise IOError("x")
            return True

        assert pol.call("pipe.a", lambda: flaky(a_calls, 2))
        assert budget.remaining == 0
        # the pool is spent: site B gets its first attempt, no retries
        with pytest.raises(IOError):
            pol.call("pipe.b", lambda: flaky(b_calls, 2))
        assert len(b_calls) == 1

    def test_attempt_timeout_retries_hung_attempt(self):
        calls = []

        def fn():
            calls.append(1)
            if len(calls) == 1:
                time.sleep(0.5)  # a hung first attempt
            return "done"

        pol = RetryPolicy(max_attempts=2, attempt_timeout_s=0.05,
                          sleep=_noop_sleep)
        assert pol.call("t.hang", fn) == "done"
        assert len(calls) == 2

    def test_attempt_timeout_exhaustion_raises_timeout(self):
        pol = RetryPolicy(max_attempts=2, attempt_timeout_s=0.05,
                          sleep=_noop_sleep)
        with pytest.raises(AttemptTimeout):
            pol.call("t.hang2", lambda: time.sleep(0.5))

    def test_attempt_timeout_polices_first_attempt_via_guarded(self):
        # guarded()'s quiet fast path must yield to the policy when a
        # configured site carries attempt_timeout_s — the FIRST attempt
        # is the one most likely to hang, and without this the guard
        # never engaged unless chaos was armed or a retry had begun
        set_policy("t.firsthang",
                   RetryPolicy(max_attempts=2, attempt_timeout_s=0.05,
                               sleep=_noop_sleep))
        calls = []

        def fn():
            calls.append(1)
            if len(calls) == 1:
                time.sleep(2.0)  # would block guarded() for 2s
            return "ok"

        t0 = time.monotonic()
        assert guarded("t.firsthang", fn) == "ok"
        assert time.monotonic() - t0 < 1.0
        assert len(calls) == 2

    def test_env_only_timeout_polices_first_attempt(self, monkeypatch):
        # a timeout configured ONLY via DMLC_TPU_RETRY must engage on
        # the very first guarded() call of a fresh process — the lazy
        # env load cannot hide behind the fast path
        monkeypatch.setenv("DMLC_TPU_RETRY",
                           "site=t.envhang,timeout=0.05,attempts=2,"
                           "base=0.0,jitter=0.0")
        reset_policies()
        calls = []

        def fn():
            calls.append(1)
            if len(calls) == 1:
                time.sleep(2.0)
            return "ok"

        t0 = time.monotonic()
        assert guarded("t.envhang", fn) == "ok"
        assert time.monotonic() - t0 < 1.0
        assert len(calls) == 2

    def test_env_contract_configures_sites(self, monkeypatch):
        monkeypatch.setenv(
            "DMLC_TPU_RETRY",
            "attempts=7,base=0.01;site=obs.*,attempts=1")
        reset_policies()
        assert policy_for("io.stream.read").max_attempts == 7
        assert policy_for("io.stream.read").base_delay_s == 0.01
        assert policy_for("obs.scrape").max_attempts == 1

    def test_env_contract_rejects_unknown_key(self, monkeypatch):
        monkeypatch.setenv("DMLC_TPU_RETRY", "nope=3")
        reset_policies()
        with pytest.raises(DMLCError, match="unknown key"):
            policy_for("any.site")

    def test_set_default_policy_flows_into_site_overrides(self):
        # site overrides are CHANGES over the current default: a
        # replaced default's sleep/backoff must reach sites that only
        # tweak attempts (obs.scrape's built-in fail-fast)
        from dmlc_tpu.resilience import set_default_policy
        slept = []
        record = slept.append
        set_default_policy(RetryPolicy(base_delay_s=0.0, sleep=record))
        pol = policy_for("obs.scrape")
        assert pol.max_attempts == 2       # the built-in change
        assert pol.sleep is record         # the new default's sleep
        # and a site with NO override is exactly the new default
        assert policy_for("io.stream.read").sleep is record
        assert policy_for("io.stream.read").base_delay_s == 0.0


# ---------------------------------------------------------------- inject

class TestFaultPlan:
    def test_parse_spec_roundtrip(self):
        spec = ("site=io.stream.read,fault=ioerror,times=2;"
                "site=gang.*,fault=crash,nth=3,rank=1,attempt=0")
        plan = FaultPlan.parse(spec, seed=5)
        assert plan.spec() == spec
        assert plan.seed == 5

    def test_parse_rejects_garbage(self):
        with pytest.raises(DMLCError, match="unknown fault"):
            FaultPlan.parse("site=x,fault=explode")
        with pytest.raises(DMLCError, match="unknown key"):
            FaultPlan.parse("site=x,fault=ioerror,frequency=2")
        with pytest.raises(DMLCError, match="site= and fault="):
            FaultPlan.parse("fault=ioerror")

    def test_times_trigger_fires_first_n(self):
        plan = FaultPlan.parse("site=a.b,fault=ioerror,times=2")
        for _ in range(2):
            with pytest.raises(IOError, match="injected fault"):
                plan.fire("a.b")
        plan.fire("a.b")  # third and later matches pass clean
        plan.fire("a.b")
        assert plan.injected == 2

    def test_nth_trigger_fires_exactly_once(self):
        plan = FaultPlan.parse("site=a.*,fault=ioerror,nth=3")
        plan.fire("a.x")
        plan.fire("a.y")
        with pytest.raises(IOError):
            plan.fire("a.z")
        plan.fire("a.x")
        assert plan.injected == 1

    def test_probability_trigger_is_seed_deterministic(self):
        def pattern(seed):
            plan = FaultPlan.parse("site=p.*,fault=ioerror,p=0.5",
                                   seed=seed)
            hits = []
            for _ in range(64):
                try:
                    plan.fire("p.x")
                    hits.append(0)
                except IOError:
                    hits.append(1)
            return hits

        assert pattern(7) == pattern(7)      # same seed => same faults
        assert pattern(7) != pattern(8)      # the seed is real
        assert 10 < sum(pattern(7)) < 54     # and it is ~a coin

    def test_rank_and_attempt_scoping(self, monkeypatch):
        monkeypatch.setenv("DMLC_TPU_TASK_ID", "1")
        monkeypatch.setenv("DMLC_TPU_ATTEMPT", "0")
        plan = FaultPlan.parse(
            "site=s,fault=ioerror,rank=1,attempt=0")
        with pytest.raises(IOError):
            plan.fire("s")
        # a restarted process (attempt bumped) runs clean
        monkeypatch.setenv("DMLC_TPU_ATTEMPT", "1")
        plan2 = FaultPlan.parse(
            "site=s,fault=ioerror,rank=1,attempt=0")
        plan2.fire("s")
        # another rank never matches
        monkeypatch.setenv("DMLC_TPU_ATTEMPT", "0")
        monkeypatch.setenv("DMLC_TPU_TASK_ID", "0")
        plan3 = FaultPlan.parse(
            "site=s,fault=ioerror,rank=1,attempt=0")
        plan3.fire("s")
        assert plan.injected == 1
        assert plan2.injected == plan3.injected == 0

    def test_delay_fault_sleeps_not_raises(self):
        plan = FaultPlan.parse(
            "site=d,fault=delay,delay_s=0.05,times=1")
        t0 = time.perf_counter()
        plan.fire("d")
        assert time.perf_counter() - t0 >= 0.04
        assert plan.events()[0]["fault"] == "delay"


# ---------------------------------------------------------------- seams

class TestInstrumentedSeams:
    def test_stream_open_retry_until_success(self, tmpfile):
        path = tmpfile("seam.bin", b"z" * 64)
        set_policy("io.stream.open",
                   RetryPolicy(max_attempts=3, sleep=_noop_sleep))
        inject.install("site=io.stream.open,fault=ioerror,times=2")
        from dmlc_tpu.io.stream import create_stream
        with create_stream(path, "r") as s:
            assert s.read_all() == b"z" * 64
        assert retry_counts()["io.stream.open"] == 2

    def test_stream_read_retry_until_success(self, tmpfile):
        path = tmpfile("seam2.bin", b"q" * 128)
        set_policy("io.stream.read",
                   RetryPolicy(max_attempts=3, sleep=_noop_sleep))
        from dmlc_tpu.io.stream import create_stream
        with create_stream(path, "r") as s:
            inject.install("site=io.stream.read,fault=ioerror,times=1")
            assert s.read_exact(128) == b"q" * 128
        assert retry_counts()["io.stream.read"] == 1

    def test_stream_read_truncation_surfaces_as_short_read(self,
                                                           tmpfile):
        path = tmpfile("seam3.bin", b"w" * 100)
        from dmlc_tpu.io.stream import create_stream
        with create_stream(path, "r") as s:
            inject.install(
                "site=io.stream.read,fault=truncate,times=1")
            # the torn read loses the tail; the framing layer's short-
            # read detection (read_exact) must catch it, not hang
            with pytest.raises(DMLCError, match="unexpected EOF"):
                s.read_exact(100)

    def test_midfile_truncation_is_eof_not_silent_shift(self, tmpfile):
        # truncation must pin the stream at EOF: with file bytes left
        # past the drop point, a mere shortening would let the next
        # read return SHIFTED bytes and read_exact would succeed with
        # silently wrong data — the exact corruption chaos exists to
        # surface, not create
        payload = bytes(range(200)) + bytes(range(56))
        path = tmpfile("seam3b.bin", payload)
        from dmlc_tpu.io.stream import create_stream
        with create_stream(path, "r") as s:
            inject.install(
                "site=io.stream.read,fault=truncate,times=1")
            with pytest.raises(DMLCError, match="unexpected EOF"):
                s.read_exact(100)

    def test_readinto_truncation_covered(self, tmpfile):
        # the in-place read path (pooled staging buffers) is part of
        # the seam too: truncation shortens the count and pins EOF
        path = tmpfile("seam3c.bin", b"r" * 100)
        from dmlc_tpu.io.stream import create_stream
        with create_stream(path, "r") as s:
            inject.install(
                "site=io.stream.read,fault=truncate,times=1")
            buf = bytearray(100)
            n = s.readinto(buf)
            assert n == 50 and bytes(buf[:n]) == b"r" * 50
            assert s.readinto(bytearray(50)) == 0  # EOF-pinned

    def test_read_retry_restores_file_position(self):
        # a buffered read that fails AFTER consuming bytes advances the
        # offset; the retried attempt must seek back or the stream
        # silently loses those bytes (shifted, wrong payloads)
        from dmlc_tpu.io.stream import FileStream

        class FlakyFile:
            def __init__(self, data):
                self.data = data
                self.pos = 0
                self.failed = False

            def read(self, n):
                if not self.failed:
                    self.failed = True
                    self.pos += 3  # consumed bytes, then the error
                    raise IOError("EIO mid-read")
                out = self.data[self.pos:self.pos + n]
                self.pos += len(out)
                return out

            def tell(self):
                return self.pos

            def seek(self, pos):
                self.pos = pos

        set_policy("io.stream.read",
                   RetryPolicy(max_attempts=3, sleep=_noop_sleep))
        s = FileStream(FlakyFile(bytes(range(64))))
        assert s.read_exact(64) == bytes(range(64))

    def test_filesys_stat_retry(self, tmpfile):
        path = tmpfile("seam4.bin", b"s")
        set_policy("io.filesys.*",
                   RetryPolicy(max_attempts=3, sleep=_noop_sleep))
        inject.install("site=io.filesys.stat,fault=ioerror,times=1")
        from dmlc_tpu.io.filesys import FileSystem, URI
        u = URI(path)
        info = FileSystem.get_instance(u).get_path_info(u)
        assert info.size == 1
        assert retry_counts()["io.filesys.stat"] == 1

    def test_spill_commit_retry(self, tmp_path):
        from dmlc_tpu.data.row_iter import RoundSpillWriter
        set_policy("spill.commit",
                   RetryPolicy(max_attempts=3, sleep=_noop_sleep))
        block = RowBlock(offset=[0, 2], label=[1.0],
                         index=np.array([0, 3], np.uint32),
                         value=[0.5, 1.5])
        w = RoundSpillWriter(str(tmp_path / "r.pages"), nparts=1)
        w.add_row([block])
        inject.install("site=spill.commit,fault=ioerror,times=2")
        f = w.commit()
        assert os.path.exists(f.path) and f.rounds == 1
        rows = list(f.iter_rows())
        assert len(rows) == 1
        np.testing.assert_array_equal(rows[0][0].index, block.index)
        assert retry_counts()["spill.commit"] == 2

    def test_checkpoint_save_restore_retry(self, tmp_path):
        set_policy("checkpoint.*",
                   RetryPolicy(max_attempts=3, sleep=_noop_sleep))
        from dmlc_tpu.io.checkpoint import load_pytree, save_pytree
        path = str(tmp_path / "ck.bin")
        inject.install("site=checkpoint.save,fault=ioerror,times=2;"
                       "site=checkpoint.restore,fault=ioerror,times=2")
        save_pytree({"a": np.arange(5)}, path)
        out = load_pytree(path)
        np.testing.assert_array_equal(out["a"], np.arange(5))
        counts = retry_counts()
        assert counts["checkpoint.save"] == 2
        assert counts["checkpoint.restore"] == 2

    def test_checkpoint_save_exhaustion_raises(self, tmp_path):
        set_policy("checkpoint.save",
                   RetryPolicy(max_attempts=2, sleep=_noop_sleep))
        from dmlc_tpu.io.checkpoint import save_pytree
        inject.install("site=checkpoint.save,fault=ioerror,times=9")
        with pytest.raises(IOError, match="injected fault"):
            save_pytree({"a": np.zeros(2)}, str(tmp_path / "ck2.bin"))

    def test_scrape_gang_retry_keeps_rank_visible(self):
        from dmlc_tpu.obs.serve import StatusServer, scrape_gang
        with StatusServer(port=0) as srv:
            inject.install("site=obs.scrape,fault=ioerror,times=1")
            merged = scrape_gang([srv.port])
            assert "unreachable" not in merged
            assert len(merged["workers"]) == 1
        assert retry_counts()["obs.scrape"] == 1

    def test_disk_row_iter_build_retries_transient_factory(
            self, tmpfile, tmp_path):
        # satellite: the page-cache build is the data-layer retry site,
        # now on resilience.RetryPolicy — a transiently failing source
        # re-parses instead of aborting the cache
        data = tmpfile("d.libsvm",
                       b"1 0:1 3:2\n0 1:1\n1 2:5 4:1\n" * 50)
        set_policy("data.pages.build",
                   RetryPolicy(max_attempts=3, sleep=_noop_sleep))
        from dmlc_tpu.data.parser import Parser
        from dmlc_tpu.data.row_iter import DiskRowIter
        calls = []

        def factory():
            calls.append(1)
            if len(calls) == 1:
                raise IOError("transient source")
            return Parser.create(data, 0, 1, format="libsvm")

        it = DiskRowIter(factory, str(tmp_path / "d.pages"))
        it.before_first()
        rows = 0
        while it.next():
            rows += it.value().size
        assert rows == 150
        assert len(calls) == 2
        assert retry_counts()["data.pages.build"] == 1

    def test_disk_row_iter_build_permanent_error_not_retried(
            self, tmp_path):
        from dmlc_tpu.data.row_iter import DiskRowIter
        calls = []

        def factory():
            calls.append(1)
            raise FileNotFoundError("no such corpus")

        with pytest.raises(FileNotFoundError):
            DiskRowIter(factory, str(tmp_path / "x.pages"))
        assert len(calls) == 1


# ------------------------------------------------------------ supervision

class TestGangSupervision:
    def test_worker_exit0_early_keeps_gang_running(self, tmp_path):
        # satellite: "exited 0 early" is a FINISHED member, not a dead
        # one — the slow worker still completes its write
        from dmlc_tpu.parallel.launch import launch_local
        script = tmp_path / "w.py"
        script.write_text(
            "import os, sys, time\n"
            "rank = os.environ['DMLC_TPU_TASK_ID']\n"
            "if rank == '0':\n"
            "    sys.exit(0)  # finishes immediately\n"
            "time.sleep(1.0)\n"
            f"open(os.path.join({str(tmp_path)!r}, 'slow-done'), "
            "'w').close()\n")
        t0 = time.monotonic()
        codes = launch_local(2, [sys.executable, str(script)],
                             timeout=60)
        assert codes == [0, 0]
        assert time.monotonic() - t0 >= 1.0
        assert (tmp_path / "slow-done").exists()

    def test_ps_roles_drained_after_workers_finish(self, tmp_path):
        # satellite: service roles wait for work forever by design;
        # "every worker exited 0" is their clean shutdown signal (the
        # pre-resilience poll loop hung on them)
        from dmlc_tpu.parallel.launch import launch_local
        script = tmp_path / "node.py"
        script.write_text(
            "import os, sys, time\n"
            "role = os.environ.get('DMLC_ROLE', 'worker')\n"
            "if role == 'worker':\n"
            "    sys.exit(0)\n"
            "time.sleep(300)  # a real scheduler/server never exits\n")
        t0 = time.monotonic()
        codes = launch_local(1, [sys.executable, str(script)],
                             num_servers=1)  # note: no timeout
        assert codes == [0, 0, 0]
        assert time.monotonic() - t0 < 60

    def test_ps_drain_beats_a_short_launch_timeout(self, tmp_path):
        # the grace window must clamp to the launch deadline: a run
        # whose every worker exited 0 must drain lingering service
        # roles and SUCCEED, not die as a misleading timeout failure
        from dmlc_tpu.parallel.launch import launch_local
        script = tmp_path / "node.py"
        script.write_text(
            "import os, sys, time\n"
            "if os.environ.get('DMLC_ROLE', 'worker') == 'worker':\n"
            "    sys.exit(0)\n"
            "time.sleep(300)\n")
        codes = launch_local(1, [sys.executable, str(script)],
                             num_servers=1, timeout=10)
        assert codes == [0, 0, 0]

    def test_restart_survives_injected_crash(self, tmp_path):
        from dmlc_tpu.parallel.launch import launch_local
        script = tmp_path / "w.py"
        script.write_text(
            "import os\n"
            "from dmlc_tpu.resilience import inject\n"
            "inject.install_if_env()\n"
            "inject.fire('work.step')\n"
            f"open(os.path.join({str(tmp_path)!r}, 'ok-'\n"
            "     + os.environ['DMLC_TPU_TASK_ID'] + '-'\n"
            "     + os.environ['DMLC_TPU_ATTEMPT']), 'w').close()\n")
        codes = launch_local(
            2, [sys.executable, str(script)], env=_gang_env(),
            faults="site=work.step,fault=crash,rank=1,attempt=0",
            restart_policy=RestartPolicy(max_restarts=2,
                                         backoff_base_s=0.05),
            timeout=120)
        assert codes == [0, 0]
        # rank 0 finished on attempt 0; rank 1 crashed (exit CRASH_EXIT)
        # and finished on attempt 1 with the same coordinates
        assert (tmp_path / "ok-0-0").exists()
        assert (tmp_path / "ok-1-1").exists()
        assert not (tmp_path / "ok-1-0").exists()
        assert CRASH_EXIT != 0

    def test_launch_faults_plan_seed_reaches_workers(self, tmp_path):
        # launch_local(faults=FaultPlan(seed=N)) must export the plan
        # seed (spec() carries clauses only) or every worker's p=
        # clauses would re-seed to 0 and the chaos schedule would not
        # reproduce the one the caller armed
        from dmlc_tpu.parallel.launch import launch_local
        script = tmp_path / "w.py"
        script.write_text(
            "import os\n"
            "from dmlc_tpu.resilience import inject\n"
            "plan = inject.install_if_env()\n"
            f"open(os.path.join({str(tmp_path)!r}, 'seed'), 'w')"
            ".write(str(plan.seed))\n")
        plan = FaultPlan.parse("site=never.fires,fault=ioerror,nth=999",
                               seed=42)
        codes = launch_local(1, [sys.executable, str(script)],
                             env=_gang_env(), faults=plan, timeout=60)
        assert codes == [0]
        assert (tmp_path / "seed").read_text() == "42"

    def test_budget_exhausted_tears_down_with_flight_bundle(
            self, tmp_path):
        from dmlc_tpu.parallel.launch import launch_local
        flight_dir = tmp_path / "flight"
        script = tmp_path / "w.py"
        script.write_text(
            "from dmlc_tpu.resilience import inject\n"
            "inject.install_if_env()\n"
            "inject.fire('work.step')\n")
        t0 = time.monotonic()
        with pytest.raises(DMLCError,
                           match="restart budget exhausted"):
            launch_local(
                1, [sys.executable, str(script)], env=_gang_env(),
                # every attempt crashes: no attempt= scope
                faults="site=work.step,fault=crash",
                restart_policy=RestartPolicy(max_restarts=1,
                                             backoff_base_s=0.05),
                flight_dir=str(flight_dir), timeout=120)
        assert time.monotonic() - t0 < 90  # teardown, not a hang
        bundles = [d for d in os.listdir(flight_dir)
                   if d.startswith("flight-")]
        assert bundles, "no launcher-side flight bundle written"
        reasons = []
        for b in bundles:
            with open(flight_dir / b / "MANIFEST.json") as f:
                reasons.append(json.load(f)["reason"])
        assert "gang_restart_budget_exhausted" in reasons


# ------------------------------------------------------- gang acceptance

_GANG_WORKER = r"""
import hashlib, os, sys
from dmlc_tpu.resilience import inject
inject.install_if_env()
from dmlc_tpu.data.parser import Parser
uri, out_dir = sys.argv[1], sys.argv[2]
rank = int(os.environ["DMLC_TPU_TASK_ID"])
nparts = int(os.environ["DMLC_TPU_NUM_WORKER"])
h = hashlib.sha256()
count = 0
p = Parser.create(uri, rank, nparts, format="libsvm", chunk_size=16384)
p.before_first()
while p.next():
    inject.fire("gang.block")      # the armed mid-epoch crash site
    h.update(p.value().copy().content_hash().encode())
    count += 1
if hasattr(p, "destroy"):
    p.destroy()
tmp = os.path.join(out_dir, f"out-{rank}.tmp")
with open(tmp, "w") as f:
    f.write(f"{count} {h.hexdigest()}\n")
os.replace(tmp, os.path.join(out_dir, f"out-{rank}.txt"))
"""


@pytest.fixture(scope="module")
def gang_data(tmp_path_factory):
    rng = np.random.RandomState(11)
    lines = [f"{i % 2} " + " ".join(
        f"{j}:{rng.rand():.5f}"
        for j in np.sort(rng.choice(400, rng.randint(2, 8),
                                    replace=False)))
        for i in range(20000)]
    p = tmp_path_factory.mktemp("resg") / "g.libsvm"
    p.write_bytes(("\n".join(lines) + "\n").encode())
    return str(p)


class TestGangCrashAcceptance:
    """ISSUE 5 acceptance: a real 2-process gang + injected mid-epoch
    crash -> auto-restart -> byte-identical epoch output, restart
    visible on /metrics and the merged gang trace."""

    def _run_gang(self, worker, data, out_dir, tmp_path, faults=None,
                  restart_policy=None, trace_dir=None):
        from dmlc_tpu.parallel.launch import launch_local
        os.makedirs(out_dir, exist_ok=True)
        return launch_local(
            2, [sys.executable, str(worker), data, out_dir],
            env=_gang_env(), faults=faults,
            restart_policy=restart_policy, trace_dir=trace_dir,
            timeout=300)

    def test_gang_survives_midepoch_crash_byte_identical(
            self, gang_data, tmp_path):
        from dmlc_tpu.obs.metrics import REGISTRY
        from dmlc_tpu.obs.serve import StatusServer
        worker = tmp_path / "gw.py"
        worker.write_text(_GANG_WORKER)
        clean_dir = str(tmp_path / "clean")
        chaos_dir = str(tmp_path / "chaos")
        trace_dir = str(tmp_path / "traces")

        # golden: the fault-free gang
        codes = self._run_gang(worker, gang_data, clean_dir, tmp_path)
        assert codes == [0, 0]
        clean = {r: open(os.path.join(clean_dir, f"out-{r}.txt"))
                 .read() for r in range(2)}
        assert all(clean.values())

        # chaos: rank 1 hard-crashes at its 3rd block, attempt 0 only
        before = REGISTRY.counter("resilience.restart").value
        codes = self._run_gang(
            worker, gang_data, chaos_dir, tmp_path,
            faults="site=gang.block,fault=crash,nth=3,rank=1,attempt=0",
            restart_policy=RestartPolicy(max_restarts=2,
                                         backoff_base_s=0.05),
            trace_dir=trace_dir)
        assert codes == [0, 0]
        chaos = {r: open(os.path.join(chaos_dir, f"out-{r}.txt"))
                 .read() for r in range(2)}
        # the restarted worker replayed its identical shard stream
        assert chaos == clean

        # the restart is visible in the launcher's /metrics ...
        assert REGISTRY.counter("resilience.restart").value \
            == before + 1
        with StatusServer(port=0) as srv:
            from urllib.request import urlopen
            with urlopen(srv.url("/metrics"), timeout=10) as resp:
                body = resp.read().decode()
        restart_lines = [
            line for line in body.splitlines()
            if line.startswith("dmlc_resilience_restart_total ")]
        assert restart_lines and \
            float(restart_lines[0].split()[1]) >= 1

        # ... and on the merged gang trace (supervisor track)
        with open(os.path.join(trace_dir, "trace-gang.json")) as f:
            merged = json.load(f)
        names = {e.get("name") for e in merged["traceEvents"]}
        assert "gang/restart/worker-1" in names
        assert any(n.startswith("gang/spawn/") for n in names)
