"""Elastic recovery (VERDICT r1 #9; SURVEY §5.3) + the rendezvous
membership plane (ROADMAP item 1).

The framework's recovery story has two layers. The DETERMINISM layer
(TestElasticRecovery): a shard stream is a pure function of (uri,
part, num_parts, seed, epoch), so a worker that dies mid-epoch can be
recovered by restarting it with the same coordinates — the
replacement replays the byte-identical record stream from the top.
The MEMBERSHIP layer (dmlc_tpu.rendezvous, the reference's
tracker.py gone elastic): a gang that loses or gains a member does
NOT restart — the rendezvous service bumps the membership epoch, the
survivors re-derive shard ownership as a pure function of (num_parts,
world, rank), and each adopted part RESUMES from the committed
progress prefix instead of replaying. Epoch-fenced progress commits
make the coverage exactly-once across any interleaving of reshards
(TestRendezvousMembership, TestElasticGangAcceptance). Documented in
docs/rendezvous.md and docs/ARCHITECTURE.md.
"""

import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

# the worker prints one line per block: "<blocks_done> <running_hash>"
_WORKER = r"""
import hashlib, sys
import jax
jax.config.update("jax_platforms", "cpu")
from dmlc_tpu.data.parser import Parser
uri, part, nparts, seed, epoch = (sys.argv[1], int(sys.argv[2]),
                                  int(sys.argv[3]), int(sys.argv[4]),
                                  int(sys.argv[5]))
h = hashlib.sha256()
p = Parser.create(uri, part, nparts, format="libsvm", chunk_size=65536)
n = 0
for _ in range(epoch + 1):       # deterministic epoch replay
    p.before_first()
    while p.next():
        h.update(p.value().copy().content_hash().encode())
        n += 1
        print(f"{n} {h.hexdigest()}", flush=True)
if hasattr(p, "destroy"):
    p.destroy()
"""

_SHUFFLE_WORKER = r"""
import hashlib, sys
import jax
jax.config.update("jax_platforms", "cpu")
from dmlc_tpu.io.input_split_shuffle import InputSplitShuffle
uri, part, nparts, seed, epoch = (sys.argv[1], int(sys.argv[2]),
                                  int(sys.argv[3]), int(sys.argv[4]),
                                  int(sys.argv[5]))
sp = InputSplitShuffle.create(uri, part, nparts, "text",
                              num_shuffle_parts=4, seed=seed)
h = hashlib.sha256()
for e in range(epoch + 1):       # epoch-reshuffled but seed-deterministic
    sp.before_first()
    n = 0
    while True:
        rec = sp.next_record()
        if rec is None:
            break
        h.update(rec)
        n += 1
        print(f"{n} {h.hexdigest()}", flush=True)
"""


@pytest.fixture(scope="module")
def data_file(tmp_path_factory):
    rng = np.random.RandomState(3)
    lines = [f"{i % 2} " + " ".join(
        f"{j}:{rng.rand():.5f}"
        for j in np.sort(rng.choice(500, rng.randint(1, 9), replace=False)))
        for i in range(30000)]
    p = tmp_path_factory.mktemp("el") / "d.libsvm"
    p.write_bytes(("\n".join(lines) + "\n").encode())
    return str(p)


def _run_worker(code, args, kill_after_lines=None, timeout=120):
    """Run the worker; optionally SIGKILL it after N progress lines.
    Returns the progress lines seen."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.pathsep.join(
               [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]
               + os.environ.get("PYTHONPATH", "").split(os.pathsep))}
    proc = subprocess.Popen([sys.executable, "-c", code] + [str(a) for a in args],
                            env=env, stdout=subprocess.PIPE, text=True)
    lines = []
    try:
        deadline = time.monotonic() + timeout
        for line in proc.stdout:
            lines.append(line.strip())
            if kill_after_lines and len(lines) >= kill_after_lines:
                os.kill(proc.pid, signal.SIGKILL)  # die mid-epoch, hard
                break
            if time.monotonic() > deadline:
                raise TimeoutError("worker too slow")
        proc.wait(timeout=30)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    return lines


class TestElasticRecovery:
    def test_killed_worker_replacement_replays_identical_stream(
            self, data_file):
        # clean run: the golden stream hash for (uri, part=1, nparts=3)
        clean = _run_worker(_WORKER, [data_file, 1, 3, 0, 0])
        assert len(clean) >= 3, "fixture should produce several blocks"
        # kill a worker HARD mid-epoch (SIGKILL: no cleanup, no flush)
        killed = _run_worker(_WORKER, [data_file, 1, 3, 0, 0],
                             kill_after_lines=1)
        assert len(killed) >= 1 and killed[0] == clean[0]
        # elastic recovery: a REPLACEMENT worker with the same
        # (uri, part, nparts, seed, epoch) replays the identical stream
        replay = _run_worker(_WORKER, [data_file, 1, 3, 0, 0])
        assert replay == clean, \
            "replacement worker diverged from the killed worker's stream"

    def test_partial_progress_is_a_prefix(self, data_file):
        # mid-stream kill leaves a PREFIX of the deterministic stream:
        # a restart can also fast-forward past already-consumed batches
        clean = _run_worker(_WORKER, [data_file, 0, 3, 0, 0])
        killed = _run_worker(_WORKER, [data_file, 0, 3, 0, 0],
                             kill_after_lines=2)
        assert killed == clean[:len(killed)]

    def test_second_epoch_stream_is_deterministic(self, data_file):
        a = _run_worker(_WORKER, [data_file, 2, 3, 0, 1])
        b = _run_worker(_WORKER, [data_file, 2, 3, 0, 1])
        assert a and a == b

    def test_shuffled_split_recovers_by_seed(self, data_file):
        # shuffled reads are ALSO recoverable: same seed => same order,
        # across a hard kill and restart
        clean = _run_worker(_SHUFFLE_WORKER, [data_file, 0, 2, 7, 1])
        assert len(clean) > 10
        _run_worker(_SHUFFLE_WORKER, [data_file, 0, 2, 7, 1],
                    kill_after_lines=3)
        replay = _run_worker(_SHUFFLE_WORKER, [data_file, 0, 2, 7, 1])
        assert replay == clean
        # different seed => different order (the shuffle is real)
        other = _run_worker(_SHUFFLE_WORKER, [data_file, 0, 2, 8, 1])
        assert other != clean


class TestRendezvousMembership:
    """The rendezvous service + client unit contracts: deterministic
    rank assignment, monotonic epoch delivery, heartbeat-grace flap
    suppression, and the epoch fence that makes progress commits
    exactly-once."""

    def test_rank_assignment_deterministic(self):
        from dmlc_tpu.rendezvous import elastic
        from dmlc_tpu.rendezvous.service import RendezvousService
        # two services fed the identical join sequence agree exactly
        with RendezvousService() as s1, RendezvousService() as s2:
            rosters = []
            for svc in (s1, s2):
                for m in ("a", "b", "c"):
                    resp = svc.handle({"op": "join", "gang": "det",
                                       "member": m, "host": "h",
                                       "port": None, "attempt": 0})
                    assert resp["ok"]
                rosters.append(resp["roster"])
            assert rosters[0] == rosters[1]
            assert [e["rank"] for e in rosters[0]] == [0, 1, 2]
        # ownership is a pure disjoint cover of the part space
        for num_parts in (1, 3, 7, 16):
            for world in (1, 2, 3, 5):
                covered = sorted(
                    p for r in range(world)
                    for p in elastic.assign_parts(num_parts, world, r))
                assert covered == list(range(num_parts))
                for r in range(world):
                    mine = elastic.assign_parts(num_parts, world, r)
                    assert mine == elastic.assign_parts(
                        num_parts, world, r)
                    for p in mine:
                        assert elastic.owner_of(p, world) == r
        # a reshard plan resumes every part exactly once, mid-prefix
        plan = elastic.reshard_plan(7, 3, {"0": 5, "3": 2})
        assert plan[0] == [(0, 5), (3, 2), (6, 0)]
        assert sorted(p for parts in plan.values()
                      for p, _ in parts) == list(range(7))

    def test_epoch_monotonic_roster_delivery(self):
        from dmlc_tpu.rendezvous.service import RendezvousService
        with RendezvousService() as svc:
            epochs = []
            script = [("join", "a"), ("join", "b"), ("join", "c"),
                      ("leave", "b"), ("report_death", "c"),
                      ("join", "a"),  # alive rejoin: NO flap
                      ("join", "d")]
            for op, member in script:
                resp = svc.handle({"op": op, "gang": "mono",
                                   "member": member, "host": "h",
                                   "port": None, "attempt": 0})
                assert resp["ok"]
                # every delivered roster has dense ranks 0..world-1
                assert [e["rank"] for e in resp["roster"]] == \
                    list(range(resp["world"]))
                epochs.append(resp["epoch"])
            # monotone, bumping on every REAL membership change and
            # holding still on the idempotent supervisor-restart rejoin
            assert epochs == [1, 2, 3, 4, 5, 5, 6]
            final = svc.handle({"op": "roster", "gang": "mono"})
            assert [e["member"] for e in final["roster"]] == ["a", "d"]

    def test_heartbeat_grace_flap_suppression(self, monkeypatch):
        from dmlc_tpu.rendezvous import RendezvousClient
        from dmlc_tpu.rendezvous import service as rsvc
        with rsvc.RendezvousService(heartbeat_grace_s=0.8) as svc:
            a = RendezvousClient("127.0.0.1", svc.port, gang="flap",
                                 member="a")
            b = RendezvousClient("127.0.0.1", svc.port, gang="flap",
                                 member="b")
            a.join()
            b.join()
            a.heartbeat()
            assert (a.epoch, a.world) == (2, 2)
            # a flaky wire: EVERY beat's first attempt fails — the
            # rendezvous.* retry seam must absorb it as a counted
            # retry, never as a membership flap
            real = rsvc.call
            calls = {"n": 0}

            def flaky(host, port, payload, timeout_s=2.0):
                calls["n"] += 1
                if calls["n"] % 2 == 1:
                    raise IOError("flaky wire")
                return real(host, port, payload, timeout_s=timeout_s)

            monkeypatch.setattr(rsvc, "call", flaky)
            for _ in range(5):
                assert a.heartbeat() and b.heartbeat()
                time.sleep(0.02)
            assert calls["n"] >= 20  # the flakiness was real
            assert (a.epoch, a.world) == (2, 2), \
                "a retried-but-delivered heartbeat flapped the roster"
            monkeypatch.setattr(rsvc, "call", real)
            # now b goes TRULY silent past the grace: one death, one
            # epoch bump, ranks compact
            deadline = time.monotonic() + 10
            while a.world != 1:
                assert time.monotonic() < deadline, \
                    "grace never reaped the silent member"
                time.sleep(0.05)
                a.heartbeat()
            assert (a.epoch, a.rank) == (3, 0)
            # the flapped member comes back: its next beat learns
            # "not in gang", auto-rejoins, and the epoch bumps again
            assert b.heartbeat()
            assert (b.epoch, b.world, b.rank) == (4, 2, 1)

    def test_fenced_commit_rejects_stale_epoch(self):
        from dmlc_tpu.rendezvous import RendezvousClient
        from dmlc_tpu.rendezvous.service import RendezvousService
        with RendezvousService() as svc:
            a = RendezvousClient("127.0.0.1", svc.port, gang="fence",
                                 member="a")
            b = RendezvousClient("127.0.0.1", svc.port, gang="fence",
                                 member="b")
            a.join()
            stale = a.epoch
            b.join()  # the roster moves; a's view is now stale
            assert a.commit(5, 10, epoch=stale) is False, \
                "a stale-fenced commit must NOT merge"
            # the rejection itself delivered the fresh view...
            assert a.epoch == b.epoch and a.world == 2
            assert a.progress.get("5", 0) == 0
            # ...under which the re-derived commit lands
            assert a.commit(5, 10, epoch=a.epoch) is True
            assert a.progress["5"] == 10

    def test_peer_tier_dead_rank_reassigns_to_survivors(self):
        from dmlc_tpu.io.objstore.peer import PeerTier
        t = PeerTier([7001, 7002, 7003], self_port=7001)
        assert t.owner_index(1) == 1
        t.mark_dead(1)
        # a dead rank costs zero probes...
        assert not t.available(1)
        # ...and its page groups round-robin over the survivors
        # [0, 2] (None == this process is the reassigned owner)
        assert [t.owner_index(g) for g in (1, 4, 7, 10)] == \
            [2, None, 2, None]
        # a roster refresh (rendezvous epoch bump) adopts the new
        # topology in place and fully resets breaker + dead state
        t.refresh([7001, 7003], self_port=7003)
        assert t.self_index == 1
        assert t.available(0) and t.available(1)
        assert t.owner_index(0) == 0 and t.owner_index(1) is None


def _consume_elastic(cli, records, out, stop, batch=3):
    """One gang member's elastic consume loop: derive ownership, the
    resume offset and the commit fence from ONE view snapshot per
    pass, read the batch, and count it consumed IFF the epoch-fenced
    commit lands — the discipline under which coverage is exactly-once
    across any interleaving of reshards."""
    from dmlc_tpu.rendezvous import elastic
    num_parts = len(records)
    while not stop.is_set():
        v = cli.view()
        if v["rank"] is None or v["epoch"] is None:
            return
        if all(int(v["progress"].get(str(p), 0)) >= len(records[p])
               for p in range(num_parts)):
            return
        progressed = False
        for p in elastic.assign_parts(num_parts, v["world"],
                                      v["rank"]):
            start = elastic.resume_skip(v["progress"], p)
            if start >= len(records[p]):
                continue
            end = min(start + batch, len(records[p]))
            chunk = records[p][start:end]
            if cli.commit(p, end, epoch=v["epoch"]):
                out.extend(chunk)
                progressed = True
            break  # one batch per pass: re-derive ownership
        if not progressed:
            cli.heartbeat()
            time.sleep(0.002)


class TestElasticGangAcceptance:
    """The two ROADMAP item-1 acceptance gangs: permanent loss →
    shrink → byte-identical exactly-once global coverage; mid-epoch
    grow → reshard visible on the merged trace, on /gang, and on the
    control ledger."""

    def test_shrink_gang_byte_identical_coverage(self):
        import hashlib
        import threading

        from dmlc_tpu.rendezvous import RendezvousClient
        from dmlc_tpu.rendezvous.service import RendezvousService
        records = {p: [f"{p}:{i}".encode() for i in range(40)]
                   for p in range(5)}
        want = sorted(r for recs in records.values() for r in recs)
        baseline = hashlib.sha256(b"\n".join(want)).hexdigest()
        outs = {m: [] for m in "abc"}
        stops = {m: threading.Event() for m in "abc"}
        # grace high: THIS gang's death is the supervisor's report,
        # deterministically timed, not a racy grace sweep
        with RendezvousService(heartbeat_grace_s=30.0) as svc:
            clis = {m: RendezvousClient("127.0.0.1", svc.port,
                                        gang="shrink", member=m)
                    for m in "abc"}
            threads = {}
            for m in "abc":
                clis[m].join()
                threads[m] = threading.Thread(
                    target=_consume_elastic,
                    args=(clis[m], records, outs[m], stops[m]),
                    daemon=True)
            for t in threads.values():
                t.start()
            # let the victim commit real mid-epoch progress, then
            # lose it PERMANENTLY: hard-stopped (a SIGKILLed process
            # commits nothing more), then reported dead by the
            # supervisor — the launch_local seam
            deadline = time.monotonic() + 30
            while len(outs["b"]) < 6:
                assert time.monotonic() < deadline, \
                    "victim never committed a batch"
                time.sleep(0.005)
            stops["b"].set()
            threads["b"].join(timeout=10)
            assert not threads["b"].is_alive()
            resp = svc.handle({"op": "report_death", "gang": "shrink",
                               "member": "b"})
            assert resp["ok"] and resp["world"] == 2
            for m in "ac":
                threads[m].join(timeout=60)
                assert not threads[m].is_alive(), \
                    f"survivor {m!r} hung after the shrink"
            assert clis["a"].world == 2
            assert clis["a"].epoch >= 4  # 3 joins + the death
            assert all(e["member"] != "b"
                       for e in clis["a"].roster)
        # the acceptance bound: byte-identical global coverage —
        # every record consumed EXACTLY once across the whole arc,
        # the victim's committed prefix reused (not replayed)
        got = sorted(outs["a"] + outs["b"] + outs["c"])
        assert got == want
        assert hashlib.sha256(b"\n".join(got)).hexdigest() == baseline
        assert outs["b"], "the victim's prefix should be real work"

    def test_grow_then_leave_resumes_committed_prefix(self):
        """The elastic 2->3->2 arc: a third member joins mid-epoch,
        commits a few batches and leaves cleanly; owners take parts
        over from the committed prefix at both reshards. Every record
        is consumed exactly once, both epoch bumps reach the gang, and
        at least one part changes hands mid-part."""
        import threading

        from dmlc_tpu.rendezvous import RendezvousClient, elastic
        from dmlc_tpu.rendezvous.service import RendezvousService
        num_parts, per_part, batch, leave_after = 6, 60, 2, 5
        ranges = {m: [] for m in "abc"}     # (part, start, end)
        worlds = []                         # (epoch, world) deliveries
        stop = threading.Event()

        def consume(cli, out, max_commits=None):
            while len(out) != max_commits and not stop.is_set():
                v = cli.view()
                if v["rank"] is None or v["epoch"] is None:
                    return
                if all(elastic.resume_skip(v["progress"], p) >= per_part
                       for p in range(num_parts)):
                    return
                progressed = False
                for p in elastic.assign_parts(num_parts, v["world"],
                                              v["rank"]):
                    start = elastic.resume_skip(v["progress"], p)
                    if start >= per_part:
                        continue
                    end = min(start + batch, per_part)
                    if cli.commit(p, end, epoch=v["epoch"]):
                        out.append((p, start, end))
                        progressed = True
                    break  # one batch per pass: re-derive ownership
                if not progressed:
                    cli.heartbeat()
                    time.sleep(0.002)

        with RendezvousService(heartbeat_grace_s=30.0) as svc:
            clis = {m: RendezvousClient("127.0.0.1", svc.port,
                                        gang="arc", member=m)
                    for m in "abc"}
            for cli in clis.values():
                cli.on_change(lambda v: worlds.append(
                    (v["epoch"], v["world"])))
            clis["a"].join()
            clis["b"].join()
            threads = {m: threading.Thread(
                target=consume, args=(clis[m], ranges[m]), daemon=True)
                for m in "ab"}
            for t in threads.values():
                t.start()
            try:
                deadline = time.monotonic() + 30
                while (len(ranges["a"]) + len(ranges["b"])) * batch * 4 \
                        < num_parts * per_part:
                    assert time.monotonic() < deadline, \
                        "the world-2 gang never consumed a quarter"
                    time.sleep(0.002)
                clis["c"].join()   # the mid-epoch grow
                consume(clis["c"], ranges["c"], max_commits=leave_after)
                clis["c"].leave()  # the clean shrink
                for m in "ab":
                    threads[m].join(timeout=60)
                    assert not threads[m].is_alive(), \
                        f"member {m!r} hung after the shrink"
            finally:
                stop.set()
        for p in range(num_parts):
            got = sorted((s, e, m) for m, rs in ranges.items()
                         for (q, s, e) in rs if q == p)
            cursor = 0
            for s, e, m in got:
                assert s == cursor, \
                    f"part {p}: gap or overlap at {s} (member {m!r})"
                cursor = e
            assert cursor == per_part, f"part {p} stops at {cursor}"
        assert len(ranges["c"]) == leave_after
        grow = [e for e, w in worlds if w == 3]
        assert grow, "the grow to world 3 never reached the gang"
        assert any(w == 2 and e > grow[0] for e, w in worlds), \
            "the shrink back to world 2 never reached the gang"
        owners = {p: {m for m, rs in ranges.items()
                      for (q, _, _) in rs if q == p}
                  for p in range(num_parts)}
        assert any(len(ms) > 1 for ms in owners.values()), \
            "no part was taken over mid-part"

    def test_grow_reshard_visible_on_trace_gang_and_ledger(self):
        import json as _json
        import urllib.request

        import dmlc_tpu.rendezvous as rndv
        from dmlc_tpu.obs import control as obs_control
        from dmlc_tpu.obs import trace as obs_trace
        from dmlc_tpu.obs.control import Controller
        from dmlc_tpu.obs.serve import StatusServer
        from dmlc_tpu.rendezvous import RendezvousClient
        from dmlc_tpu.rendezvous.service import RendezvousService

        rec = obs_trace.start()
        ctl = obs_control.install(Controller())
        svc = srv = None
        try:
            svc = RendezvousService(heartbeat_grace_s=30.0)
            a = RendezvousClient("127.0.0.1", svc.port, gang="grow",
                                 member="a", serve_port=7101)
            b = RendezvousClient("127.0.0.1", svc.port, gang="grow",
                                 member="b", serve_port=7102)
            a.join()
            b.join()
            a.heartbeat()
            assert (a.epoch, a.world) == (2, 2)
            rndv.install(client=a)  # a's membership IS /gang here
            srv = StatusServer(port=0)
            # the mid-epoch GROW: a third member joins the running
            # gang; a learns at its next beat and reshards
            c = RendezvousClient("127.0.0.1", svc.port, gang="grow",
                                 member="c", serve_port=7103)
            c.join()
            assert (c.world, c.rank) == (3, 2)
            a.heartbeat()
            assert a.world == 3
            # 1) the merged trace: service-side join instants AND the
            # member-side reshard instant, with the world transition
            names = [e[1] for e in rec.events()]
            assert "gang/member/join" in names
            assert "gang/member/reshard" in names
            ev = [e for e in rec.events()
                  if e[1] == "gang/member/reshard"][-1]
            assert ev[6]["old_world"] == 2
            assert ev[6]["new_world"] == 3
            # 2) /gang: the live roster over HTTP
            with urllib.request.urlopen(srv.url("/gang"),
                                        timeout=5) as r:
                doc = _json.loads(r.read())
            mem = doc["membership"]
            assert mem["world"] == 3 and mem["epoch"] == a.epoch
            assert [m["member"] for m in mem["roster"]] == \
                ["a", "b", "c"]
            # 3) the control ledger: a schema-valid membership record
            recs = [r for r in ctl.ledger.records()
                    if r["family"] == "gang"]
            assert recs and recs[-1]["outcome"] == "reshard"
            assert (recs[-1]["old"], recs[-1]["new"]) == (2, 3)
            assert recs[-1]["verdict_id"] == f"m{a.epoch}-grow"
        finally:
            rndv.uninstall()
            obs_control.uninstall()
            obs_trace.stop()
            if srv is not None:
                srv.close()
            if svc is not None:
                svc.close()
