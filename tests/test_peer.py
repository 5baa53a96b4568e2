"""The gang-scale data plane (ROADMAP item 5): the ``/pages`` peer
endpoint, the objstore peer hydration tier, singleflight dedup, chaos
degradation — and THE acceptance: a REAL 2-process gang whose cold
``obj://`` epoch moves ~1/N of the single-rank wire bytes, goes
wire-free warm on every rank, and streams byte-identical to local."""

import json
import os
import socket
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import dmlc_tpu.io.objstore as objstore
from dmlc_tpu.io.objstore import peer as peer_mod
from dmlc_tpu.io.pagestore import ENV_STORE_DIR, PageStore
from dmlc_tpu.io.stream import create_seek_stream_for_read
from dmlc_tpu.obs.metrics import REGISTRY
from dmlc_tpu.obs.serve import StatusServer
from dmlc_tpu.resilience import (
    RetryPolicy, inject, reset_policies, set_policy,
)


def _counter(name):
    return REGISTRY.counter(name).value


def _noop_sleep(_s):
    pass


def _get(url, headers=None, timeout=10.0):
    req = urllib.request.Request(url, headers=headers or {})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, r.read(), dict(r.headers.items())


def _payload(rows=6000, seed=0):
    rng = np.random.RandomState(seed)
    return b"".join(b"%d %d:%.4f %d:%.4f\n"
                    % (i % 2, rng.randint(0, 40), rng.rand(),
                       40 + rng.randint(0, 40), rng.rand())
                    for i in range(rows))


@pytest.fixture
def plane(tmp_path, monkeypatch):
    """An isolated objstore plane: fresh emulator, per-test LOCAL page
    store root (via the DMLC_TPU_PAGESTORE_DIR satellite env), small
    blocks, peer tier reset on both sides."""
    import dmlc_tpu.io.objstore.fs as ofs
    monkeypatch.delenv(ofs.ENV_ROOT, raising=False)
    monkeypatch.delenv("DMLC_TPU_SERVE_PORTS", raising=False)
    monkeypatch.delenv("DMLC_TPU_SERVE_PORT", raising=False)
    monkeypatch.setenv(ENV_STORE_DIR, str(tmp_path / "local-store"))
    saved = ofs.options()
    client = objstore.configure(root=str(tmp_path / "objroot"),
                                block_bytes=1 << 15, coalesce=2,
                                parallel=2)
    peer_mod.reset()
    yield client, tmp_path
    objstore.configure(None, block_bytes=saved["block_bytes"],
                       coalesce=saved["coalesce"],
                       parallel=saved["parallel"],
                       hydrate=saved["hydrate"],
                       peer=saved.get("peer", True))
    peer_mod.reset()
    inject.uninstall()
    reset_policies()


def _hydrate_into(root, uri, payload_len):
    """Fill the page store at ``root`` by streaming the object with
    that store (the 'peer rank already read this' state)."""
    store = PageStore.at(str(root))
    s = create_seek_stream_for_read(uri)
    s._store = store  # this stream hydrates the PEER's store
    s._peer = None
    out = s.read_all()
    s.close()
    assert len(out) == payload_len
    return store


# ------------------------------------------------------ /pages endpoint

class TestPagesEndpoint:
    def test_serves_committed_entry_with_headers(self, plane):
        em, tmp = plane
        em.put("b", "x.bin", b"E" * 50000)
        store = _hydrate_into(tmp / "peer-store", "obj://b/x.bin",
                              50000)
        entries = sorted(n for n in os.listdir(store.root)
                         if n.endswith(".pages"))
        assert entries
        served0 = _counter("objstore.peer.served")
        with StatusServer(pages_root=store.root) as srv:
            status, body, headers = _get(
                srv.url(f"/pages/{entries[0]}"))
            assert status == 200
            stamp = store.stamp(entries[0])
            assert json.loads(headers["X-Dmlc-Fingerprint"]) == \
                stamp["fingerprint"]
            assert headers["X-Dmlc-Codec"] == stamp.get("codec", "raw")
            # the stored bytes verbatim (here: raw codec level 0)
            assert body == (b"E" * 50000)[:1 << 15]
            # ranged read of the STORED entry bytes
            status, part, headers = _get(
                srv.url(f"/pages/{entries[0]}"),
                headers={"Range": "bytes=10-19"})
            assert status == 206 and part == body[10:20]
            assert headers["Content-Range"] == \
                f"bytes 10-19/{len(body)}"
        assert _counter("objstore.peer.served") >= served0 + 2

    def test_unknown_and_unsafe_names_404(self, plane):
        em, tmp = plane
        (tmp / "peer-store").mkdir()
        with StatusServer(pages_root=str(tmp / "peer-store")) as srv:
            for name in ("ghost.pages", "..%2Fescape", ".hidden",
                         "a%5Cb.pages"):
                with pytest.raises(urllib.error.HTTPError) as e:
                    _get(srv.url(f"/pages/{name}"))
                assert e.value.code == 404
            with pytest.raises(urllib.error.HTTPError) as e:
                _get(srv.url("/pages/"))
            assert e.value.code == 404

    def test_uncommitted_bare_file_not_served(self, plane):
        """A file without a committed sidecar stamp (a tmp, an alien
        file) is never handed to a peer."""
        em, tmp = plane
        root = tmp / "peer-store"
        root.mkdir()
        (root / "bare.pages").write_bytes(b"x" * 100)
        with StatusServer(pages_root=str(root)) as srv:
            with pytest.raises(urllib.error.HTTPError) as e:
                _get(srv.url("/pages/bare.pages"))
            assert e.value.code == 404

    def test_stale_fingerprint_rejected_serverside(self, plane):
        """The object changed under the hydrated page: the server
        re-stats the stamped fingerprint and answers 404 — a peer can
        degrade to the wire, it must never serve a stale page."""
        em, tmp = plane
        em.put("b", "st.bin", b"A" * 40000)
        store = _hydrate_into(tmp / "peer-store", "obj://b/st.bin",
                              40000)
        entries = [n for n in os.listdir(store.root)
                   if n.endswith(".pages")]
        em.put("b", "st.bin", b"A" * 40001)  # size change = stale
        with StatusServer(pages_root=store.root) as srv:
            with pytest.raises(urllib.error.HTTPError) as e:
                _get(srv.url(f"/pages/{entries[0]}"))
            assert e.value.code == 404
            assert b"stale" in e.value.read()

    def test_freshness_verdict_cached_across_requests(self, plane):
        """Serving the same entry repeatedly within the TTL re-stats
        the origin ONCE — the per-block HEAD must not erode the 1/N
        wire saving the tier delivers (a stale page is still rejected
        at the first judgment, and entry names are etag-keyed)."""
        em, tmp = plane
        em.put("b", "ttl.bin", b"T" * 30000)
        store = _hydrate_into(tmp / "peer-store", "obj://b/ttl.bin",
                              30000)
        entry = [n for n in os.listdir(store.root)
                 if n.endswith(".pages")][0]
        with StatusServer(pages_root=store.root) as srv:
            em.reset_counters()
            for _ in range(4):
                status, _, _ = _get(srv.url(f"/pages/{entry}"))
                assert status == 200
            assert em.counters()["heads"] <= 1, \
                "every /pages serve re-statted the origin"

    def test_bad_range_416(self, plane):
        em, tmp = plane
        em.put("b", "r.bin", b"R" * 1000)
        store = _hydrate_into(tmp / "peer-store", "obj://b/r.bin", 1000)
        entry = [n for n in os.listdir(store.root)
                 if n.endswith(".pages")][0]
        with StatusServer(pages_root=store.root) as srv:
            with pytest.raises(urllib.error.HTTPError) as e:
                _get(srv.url(f"/pages/{entry}"),
                     headers={"Range": "bytes=5000-"})
            assert e.value.code == 416


class TestConcurrentScrape:
    def test_slow_pages_transfer_does_not_starve_healthz(self, plane):
        """The ThreadingHTTPServer pin (satellite): a /pages body
        transfer stuck behind a non-reading client runs on its own
        handler thread; /healthz and /metrics stay live meanwhile."""
        em, tmp = plane
        root = tmp / "peer-store"
        store = PageStore.at(str(root))
        w = store.writer("big.pages", fingerprint=None,
                         meta={"codec": "raw"})
        w.write(os.urandom(8 << 20))  # larger than any socket buffer
        w.commit()
        with StatusServer(pages_root=str(root)) as srv:
            # a hand-rolled client that requests the big page and then
            # stops reading — the handler blocks in wfile.write
            sock = socket.create_connection(("127.0.0.1", srv.port),
                                            timeout=10)
            try:
                sock.sendall(b"GET /pages/big.pages HTTP/1.1\r\n"
                             b"Host: localhost\r\n\r\n")
                first = sock.recv(1024)  # headers arrived; body huge
                assert b"200" in first.split(b"\r\n", 1)[0]
                t0 = time.perf_counter()
                status, body, _ = _get(srv.url("/healthz"), timeout=5)
                dt = time.perf_counter() - t0
                assert status == 200 and json.loads(body)["ok"]
                assert dt < 5.0
                status, _, _ = _get(srv.url("/metrics"), timeout=5)
                assert status == 200
            finally:
                sock.close()


# --------------------------------------------------------- singleflight

class TestSingleflight:
    def test_concurrent_cold_readers_dedup_onto_one_fetch(self, plane):
        """Two threads cold-read the same object at once: singleflight
        elects one leader per hydration group, the follower reads the
        committed page — the emulator sees roughly ONE stream's worth
        of GET bytes, not two."""
        em, tmp = plane
        em.latency_s = 0.002  # a leader fetch takes real time, so the
        # second thread reliably arrives while it is in flight
        payload = _payload(rows=20000)
        em.put("b", "sf.bin", payload)
        em.reset_counters()
        dedup0 = _counter("pagestore.singleflight.dedup")
        barrier = threading.Barrier(2)
        results = [None, None]

        def read(ix):
            s = create_seek_stream_for_read("obj://b/sf.bin")
            barrier.wait()
            results[ix] = s.read_all()
            s.close()

        threads = [threading.Thread(target=read, args=(i,))
                   for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert results[0] == payload and results[1] == payload
        assert _counter("pagestore.singleflight.dedup") > dedup0
        # strictly less than two full fetches — the dedup is real
        assert em.counters()["get_bytes"] < 2 * len(payload)

    def test_follower_whose_block_missed_fetches_itself(self, plane):
        """A follower that waited but finds no committed page (the
        leader's span stopped short) fetches on its own — dedup is an
        optimization, never a correctness dependency."""
        em, tmp = plane
        em.put("b", "solo.bin", b"Q" * 100000)
        s = create_seek_stream_for_read("obj://b/solo.bin")
        import dmlc_tpu.io.objstore.fs as ofs
        key = (s._entry_prefix, s._bb, 0)
        assert ofs._SINGLEFLIGHT.lead(key)  # occupy the leader slot
        try:
            done = threading.Event()
            out = []

            def follower():
                out.append(s.read(10))
                done.set()

            th = threading.Thread(target=follower)
            th.start()
            time.sleep(0.1)
            assert not done.is_set()  # follower parked behind leader
        finally:
            ofs._SINGLEFLIGHT.done(key)
        th.join(timeout=30)
        assert out == [b"Q" * 10]
        s.close()


# ------------------------------------------------------- the peer tier

class TestPeerTier:
    def _peer_server(self, em, tmp, uri, size):
        store = _hydrate_into(tmp / "peer-store", uri, size)
        srv = StatusServer(pages_root=store.root)
        return store, srv

    def test_blocks_served_from_peer_not_wire(self, plane):
        em, tmp = plane
        payload = _payload(rows=12000)
        em.put("b", "p.bin", payload)
        store, srv = self._peer_server(em, tmp, "obj://b/p.bin",
                                       len(payload))
        try:
            peer_mod.configure(ports=[srv.port])
            g0, pg0 = _counter("objstore.get"), \
                _counter("objstore.peer.get")
            em.reset_counters()
            s = create_seek_stream_for_read("obj://b/p.bin")
            assert s.read_all() == payload
            s.close()
            assert em.counters()["gets"] == 0, \
                "peer-owned blocks must not touch the wire"
            assert _counter("objstore.peer.get") > pg0
            assert _counter("objstore.get") == g0
            # and the peer-fetched blocks hydrated LOCALLY: a second
            # epoch is free of both the wire AND the peer
            pg1 = _counter("objstore.peer.get")
            s = create_seek_stream_for_read("obj://b/p.bin")
            assert s.read_all() == payload
            s.close()
            assert em.counters()["gets"] == 0
            assert _counter("objstore.peer.get") == pg1
        finally:
            srv.close()

    def test_peer_off_option_skips_tier(self, plane):
        em, tmp = plane
        payload = b"n" * 80000
        em.put("b", "off.bin", payload)
        store, srv = self._peer_server(em, tmp, "obj://b/off.bin",
                                       len(payload))
        try:
            peer_mod.configure(ports=[srv.port])
            objstore.configure(peer=False)
            em.reset_counters()
            s = create_seek_stream_for_read("obj://b/off.bin")
            assert s.read_all() == payload
            s.close()
            assert em.counters()["gets"] > 0  # straight to the wire
        finally:
            objstore.configure(peer=True)
            srv.close()

    def test_chaos_ioerror_degrades_to_wire_byte_identical(self, plane):
        em, tmp = plane
        payload = _payload(rows=9000)
        em.put("b", "ch.bin", payload)
        store, srv = self._peer_server(em, tmp, "obj://b/ch.bin",
                                       len(payload))
        try:
            peer_mod.configure(ports=[srv.port])
            set_policy("io.objstore.peer",
                       RetryPolicy(max_attempts=2, sleep=_noop_sleep))
            inject.install("site=io.objstore.peer,fault=ioerror")
            m0 = _counter("objstore.peer.miss")
            em.reset_counters()
            s = create_seek_stream_for_read("obj://b/ch.bin")
            assert s.read_all() == payload, \
                "chaos at the peer tier corrupted the stream"
            s.close()
            assert em.counters()["gets"] > 0, "wire fallback missing"
            assert _counter("objstore.peer.miss") > m0
        finally:
            srv.close()

    def test_chaos_truncate_degrades_to_wire_byte_identical(self,
                                                            plane):
        em, tmp = plane
        payload = _payload(rows=9000)
        em.put("b", "tr.bin", payload)
        store, srv = self._peer_server(em, tmp, "obj://b/tr.bin",
                                       len(payload))
        try:
            peer_mod.configure(ports=[srv.port])
            set_policy("io.objstore.peer",
                       RetryPolicy(max_attempts=2, sleep=_noop_sleep))
            inject.install("site=io.objstore.peer,fault=truncate")
            em.reset_counters()
            s = create_seek_stream_for_read("obj://b/tr.bin")
            assert s.read_all() == payload, \
                "a torn peer payload leaked downstream"
            s.close()
            assert em.counters()["gets"] > 0
        finally:
            srv.close()

    def test_stale_peer_page_rejected_and_refetched(self, plane):
        """A peer serving a page whose stamp does NOT match this
        reader's fingerprint (here: an unstamped commit the server
        cannot judge) is rejected CLIENT-side and the block refetched
        from the wire — byte-identical, never the stale bytes."""
        em, tmp = plane
        payload = b"G" * 90000
        em.put("b", "stale.bin", payload)
        store, srv = self._peer_server(em, tmp, "obj://b/stale.bin",
                                       len(payload))
        # falsify every peer entry: plausible bytes, no fingerprint —
        # the server serves (freshness unknowable), the client must
        # reject on fingerprint mismatch
        for name in os.listdir(store.root):
            if name.endswith(".pages"):
                store._stamp_entry(name, {"fingerprint": None,
                                          "codec": "raw"})
        try:
            peer_mod.configure(ports=[srv.port])
            set_policy("io.objstore.peer",
                       RetryPolicy(max_attempts=2, sleep=_noop_sleep))
            served0 = _counter("objstore.peer.served")
            g0 = _counter("objstore.peer.get")
            em.reset_counters()
            s = create_seek_stream_for_read("obj://b/stale.bin")
            assert s.read_all() == payload
            s.close()
            assert em.counters()["gets"] > 0, "wire refetch missing"
            assert _counter("objstore.peer.served") > served0, \
                "server never served (test exercised nothing)"
            assert _counter("objstore.peer.get") == g0, \
                "client accepted a stale-stamped peer page"
        finally:
            srv.close()

    def test_dead_peer_breaker_bounds_probes_no_hang(self, plane):
        em, tmp = plane
        payload = b"D" * 200000  # several groups
        em.put("b", "dead.bin", payload)
        # a port with nobody listening: every peer fetch fails fast
        sock = socket.socket()
        sock.bind(("127.0.0.1", 0))
        dead_port = sock.getsockname()[1]
        sock.close()
        peer_mod.configure(ports=[dead_port], breaker_failures=2,
                           breaker_snooze_s=60.0)
        set_policy("io.objstore.peer",
                   RetryPolicy(max_attempts=2, sleep=_noop_sleep))
        t0 = time.perf_counter()
        s = create_seek_stream_for_read("obj://b/dead.bin")
        assert s.read_all() == payload
        s.close()
        assert time.perf_counter() - t0 < 30.0, "dead peer ~= hang"
        tier = peer_mod.tier()
        assert tier is not None and not tier.available(0), \
            "breaker never opened on a dead peer"

    def test_tier_env_contract(self, plane, monkeypatch):
        em, tmp = plane
        monkeypatch.setenv("DMLC_TPU_SERVE_PORTS", "7001,7002,7003")
        monkeypatch.setenv("DMLC_TPU_SERVE_PORT", "7002")
        peer_mod.reset()
        t = peer_mod.tier()
        assert t is not None and t.world == 3 and t.self_index == 1
        assert t.remote_count == 2
        # group ownership round-robins; OUR groups return None
        assert t.owner_index(0) == 0
        assert t.owner_index(1) is None
        assert t.owner_index(2) == 2
        peer_mod.reset()
        monkeypatch.setenv("DMLC_TPU_SERVE_PORTS", "7001")
        assert peer_mod.tier() is None  # a gang of one has no peers
        # a MANGLED gang list must not crash the first obj:// read —
        # warn once, run tierless consistently
        peer_mod.reset()
        monkeypatch.setenv("DMLC_TPU_SERVE_PORTS", "9100,910x")
        assert peer_mod.tier() is None
        assert peer_mod.tier() is None  # and stays consistent


# ------------------------------------------- evidence + CLI satellites

class TestPeerTelemetrySurfaces:
    def test_analyze_names_peer_vs_wire_served(self):
        from dmlc_tpu.obs.analyze import attribute
        snap = {"wall_s": 2.0, "epoch": 1,
                "stages": [{"name": "parse", "kind": "parse",
                            "wait_s": 1.5, "bytes": 10 ** 9}]}
        metrics = {"counters": {"objstore.get": 4,
                                "objstore.bytes": 10 ** 9,
                                "objstore.bytes_served": 10 ** 9,
                                "objstore.peer.get": 7,
                                "objstore.peer.bytes": 5 * 10 ** 8,
                                "objstore.peer.miss": 1,
                                "pagestore.hit": 0,
                                "pagestore.miss": 8}}
        v = attribute(snap, metrics=metrics)
        line = next((e for e in v["evidence"]
                     if e.startswith("peer tier:")), None)
        assert line is not None, v["evidence"]
        assert "7 peer GETs" in line
        assert "peer-served vs" in line and "wire-served" in line
        # no peer counters -> no fabricated evidence line
        v2 = attribute(snap, metrics={"counters":
                                      {"objstore.get": 4}})
        assert not any(e.startswith("peer tier:")
                       for e in v2["evidence"])

    def test_obsctl_gang_renders_byte_split(self, monkeypatch, capsys):
        import importlib
        import sys as _sys
        _sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))), "scripts"))
        obsctl = importlib.import_module("obsctl")
        view = {
            "schema": 1, "period_s": 0.5, "host": "127.0.0.1",
            "ports": [9100, 9101], "polls": 4,
            "ranks": {
                "rank0": {"port": 9100, "rank": 0,
                          "unreachable": False, "last_error": None,
                          "last_poll_t": 1.0, "polls_ok": 4,
                          "polls_failed": 0, "gaps": [],
                          "series": {"kept": 4, "samples": [
                              {"t": 1.0, "v": {
                                  "counters.objstore.bytes": 500.0,
                                  "counters.objstore.peer.bytes": 0.0,
                                  "counters.objstore.peer."
                                  "served_bytes": 400.0}}]}},
                "rank1": {"port": 9101, "rank": 1,
                          "unreachable": False, "last_error": None,
                          "last_poll_t": 1.0, "polls_ok": 4,
                          "polls_failed": 0, "gaps": [],
                          "series": {"kept": 4, "samples": [
                              {"t": 1.0, "v": {
                                  "counters.objstore.bytes": 100.0,
                                  "counters.objstore.peer.bytes":
                                      400.0}}]}},
            },
            "rollup": {"samples": [
                {"t": 1.0, "v": {"gang.reachable": 2.0,
                                 "gang.expected": 2.0,
                                 "sum.counters.objstore.bytes": 600.0,
                                 "sum.counters.objstore.peer.bytes":
                                     400.0}}]},
        }
        monkeypatch.setattr(obsctl, "_fetch",
                            lambda *a, **k: view)
        rc = obsctl.main(["gang", "--port", "1"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "bytes: wire 500 · peer-served 0 · " \
               "served-to-peers 400" in out
        assert "bytes: wire 100 · peer-served 400" in out
        assert "rollup bytes: wire 600 · peer-served 400" in out


class TestStoreDirEnv:
    def test_default_store_dir_honors_env(self, monkeypatch, tmp_path):
        from dmlc_tpu.io import pagestore
        monkeypatch.setenv(ENV_STORE_DIR, str(tmp_path / "mine"))
        assert pagestore.default_store_dir() == str(tmp_path / "mine")
        monkeypatch.delenv(ENV_STORE_DIR)
        assert pagestore.default_store_dir().endswith("dmlc_tpu_spill")


# ------------------------------------------------- THE gang acceptance

class TestGangAcceptance:
    def test_two_rank_gang_splits_wire_and_goes_warm(self, tmp_path):
        """A REAL 2-process gang over one obj:// object: cold epoch
        wire bytes ≈ corpus/2 per rank (the 1/N tentpole), both peer
        counters live, warm epoch zero-GET everywhere, every stream
        sha256-identical to the local bytes."""
        import hashlib
        import sys

        from dmlc_tpu.parallel.launch import launch_local

        payload = _payload(rows=30000)  # ~1 MB
        objroot = tmp_path / "objroot"
        em = objstore.configure(root=str(objroot))
        try:
            em.put("bench", "gang.libsvm", payload)
        finally:
            objstore.configure(None)
        local_hash = hashlib.sha256(payload).hexdigest()
        worker = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "peer_worker.py")
        out_dir = tmp_path / "gang"
        out_dir.mkdir()
        env = {
            "DMLC_TPU_OBJSTORE_ROOT": str(objroot),
            "PYTHONPATH": os.pathsep.join(
                [os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__)))]
                + [p for p in os.environ.get(
                    "PYTHONPATH", "").split(os.pathsep) if p]),
        }
        codes = launch_local(
            2, [sys.executable, worker, "obj://bench/gang.libsvm",
                str(out_dir), str(1 << 16), "2"],
            env=env, serve_ports=True, timeout=180)
        assert codes[:2] == [0, 0]
        results = []
        for rank in range(2):
            with open(out_dir / f"peer-{rank}.json") as f:
                results.append(json.load(f))
        size = len(payload)
        for r in results:
            assert r["cold"]["sha256"] == local_hash
            assert r["warm"]["sha256"] == local_hash
            assert r["warm"]["counters"]["objstore.get"] == 0, \
                f"rank {r['rank']} warm epoch hit the wire"
            assert r["warm"]["counters"]["objstore.peer.get"] == 0, \
                f"rank {r['rank']} warm epoch hit the peer"
            assert r["cold"]["counters"]["objstore.peer.bytes"] > 0
            wired = r["cold"]["counters"]["objstore.bytes"]
            assert wired <= 0.60 * size, \
                (f"rank {r['rank']} moved {wired}/{size} wire bytes —"
                 " the peer tier did not carry its half")
        total = sum(r["cold"]["counters"]["objstore.bytes"]
                    for r in results)
        assert 0.9 * size <= total <= 1.2 * size, \
            f"gang total wire bytes {total} vs corpus {size}"
