"""Cross-rank distributed request tracing (dmlc_tpu.obs.rpc): the
trace-context wire format, Perfetto flow-event golden keys, the
per-(peer, verb) RPC edge table and its /rpc endpoint, per-attempt
chaos spans, the traced rendezvous/scrape edges, the tracing-off
overhead gate, and THE acceptance — a real 2-process gang whose merged
timeline carries one flow-linked client/server span pair per edge
type (peer /pages, objstore GET, rendezvous commit)."""

import json
import os
import sys
import tempfile
import time
import urllib.request

import pytest

import dmlc_tpu.io.objstore as objstore
from dmlc_tpu.io.stream import create_seek_stream_for_read
from dmlc_tpu.obs import rpc
from dmlc_tpu.obs import trace as obs_trace
from dmlc_tpu.obs.export import chrome_events
from dmlc_tpu.resilience import inject


@pytest.fixture(autouse=True)
def _clean_plane():
    """Every test starts with a quiet tracing plane and an empty edge
    table, and cannot leak a live recorder into its neighbours. A
    REGISTRY.reset() elsewhere in the suite drops the import-time
    collector registration — restore it so snapshot-shape tests hold
    regardless of ordering."""
    from dmlc_tpu.obs.metrics import REGISTRY
    if "rpc" not in REGISTRY.snapshot()["collectors"]:
        REGISTRY.register("rpc", rpc.EDGES, rpc.RpcEdgeTable.stats)
    rpc.EDGES.reset()
    yield
    if obs_trace.active() is not None:
        obs_trace.stop()
    rpc.EDGES.reset()
    objstore.configure(None)


def _client_spans(evs, verb=None):
    out = [e for e in evs if e.get("cat") == rpc._trace.CAT_RPC_CLIENT]
    if verb is not None:
        out = [e for e in out if e["args"]["verb"] == verb]
    return out


def _server_spans(evs):
    return [e for e in evs if e.get("cat") == rpc._trace.CAT_RPC_SERVER]


def _settle(rec, pred, timeout_s=5.0):
    """Server spans land from the HANDLER thread after the response is
    on the wire — poll the live recorder until the pair shows up."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        evs = chrome_events(rec)
        if pred(evs):
            return evs
        time.sleep(0.01)
    return chrome_events(rec)


class TestTraceContext:
    def test_roundtrip_and_wire_form(self):
        ctx = rpc.new_context()
        assert len(ctx.trace_id) == 16 and len(ctx.span_id) == 8
        wire = rpc.serialize(ctx)
        assert wire == f"{ctx.trace_id}-{ctx.span_id}"
        assert rpc.parse(wire) == ctx

    def test_operation_pins_trace_id(self):
        obs_trace.start()
        try:
            with rpc.operation("io.objstore.get") as tid:
                a = rpc.new_context(tid)
                b = rpc.new_context(tid)
            assert a.trace_id == b.trace_id == tid
            assert a.span_id != b.span_id
        finally:
            obs_trace.stop()

    def test_parse_tolerates_garbage(self):
        for junk in (None, 42, "", "nodash", "-", "a-", "-b",
                     b"aaaa-bbbb", ["x-y"]):
            assert rpc.parse(junk) is None

    def test_inject_extract_header_and_field(self):
        ctx = rpc.new_context()
        hdrs = {}
        rpc.inject(ctx, hdrs)
        assert hdrs == {rpc.TRACE_HEADER: rpc.serialize(ctx)}
        assert rpc.extract(hdrs) == ctx
        payload = {"op": "join"}
        rpc.inject(ctx, payload, key=rpc.TRACE_FIELD)
        assert rpc.extract(payload, key=rpc.TRACE_FIELD) == ctx
        # carriers without .get (or missing keys) are None, not raises
        assert rpc.extract(object()) is None
        assert rpc.extract({}) is None

    def test_off_cost_mints_nothing(self):
        assert obs_trace.active() is None
        with rpc.operation("io.objstore.get") as op:
            assert op is None
            with rpc.client_span("get", "emulator") as call:
                assert call is None
                assert rpc.active_call() is None
        assert rpc.EDGES.view()["edges"] == []


class TestFlowEventGolden:
    """Golden: the Perfetto flow-event shape is pinned like the PR 3
    chrome golden — ph "s" inside the client slice, ph "f" + bp "e"
    inside the server slice, both bound by id == trace_id."""

    def _trace_one_pair(self):
        rec = obs_trace.start()
        with rpc.operation("io.objstore.get"):
            with rpc.client_span("get", "127.0.0.1:9") as call:
                ctx = call.ctx
                with rpc.emulated_server("get"):
                    time.sleep(0.002)
        obs_trace.stop()
        return chrome_events(rec), ctx

    def test_flow_golden_keys(self):
        evs, ctx = self._trace_one_pair()
        flows = [e for e in evs if e.get("name") == "rpc.flow"]
        assert len(flows) == 2
        start = [f for f in flows if f["ph"] == "s"]
        finish = [f for f in flows if f["ph"] == "f"]
        assert len(start) == 1 and len(finish) == 1
        for f in flows:
            for key in ("name", "cat", "id", "pid", "tid", "ts", "ph"):
                assert key in f, (key, f)
            assert f["cat"] == "rpc"
            # bound by trace_id ONLY: retried attempts share the chain
            assert f["id"] == ctx.trace_id
        assert finish[0]["bp"] == "e"
        assert "bp" not in start[0]

    def test_flow_ts_matches_owning_slice(self):
        evs, ctx = self._trace_one_pair()
        cl = _client_spans(evs, "get")[0]
        sv = _server_spans(evs)[0]
        flows = {f["ph"]: f for f in evs if f.get("name") == "rpc.flow"}
        assert flows["s"]["ts"] == cl["ts"]
        assert flows["f"]["ts"] == sv["ts"]
        # the span pair itself is bound by the serialized context
        assert cl["args"][rpc.TRACE_FIELD] == sv["args"][rpc.TRACE_FIELD]

    def test_no_flow_without_context(self):
        rec = obs_trace.start()
        with obs_trace.span("stage", "pipeline"):
            pass
        obs_trace.stop()
        assert [e for e in chrome_events(rec)
                if e.get("name") == "rpc.flow"] == []


class TestEdgeTable:
    def test_percentiles_and_residual(self):
        t = rpc.RpcEdgeTable()
        for i in range(100):
            # client 1000..1099us, server flat 400us
            t.observe("peer:1", "get", 1000.0 + i, 400.0)
        (edge,) = t.view()["edges"]
        assert edge["count"] == 100 and edge["errors"] == 0
        assert edge["attributed"] == 100
        assert edge["client_us"]["p50"] == pytest.approx(1050, abs=2)
        assert edge["client_us"]["p99"] == pytest.approx(1099, abs=1)
        assert edge["server_us"]["p50"] == 400.0
        assert edge["residual_us"]["p50"] == pytest.approx(650, abs=2)

    def test_residual_clamped_at_zero(self):
        t = rpc.RpcEdgeTable()
        t.observe("p", "get", 100.0, 250.0)  # clock skew: server > client
        (edge,) = t.view()["edges"]
        assert edge["residual_us"]["p50"] == 0.0

    def test_unattributed_edge_has_no_server_stats(self):
        t = rpc.RpcEdgeTable()
        t.observe("p", "stat", 50.0)
        (edge,) = t.view()["edges"]
        assert edge["attributed"] == 0
        assert edge["server_us"] is None and edge["residual_us"] is None

    def test_bounded_cardinality_folds_to_other(self):
        t = rpc.RpcEdgeTable(max_edges=4)
        for i in range(10):
            t.observe(f"peer:{i}", "get", 10.0)
        doc = t.view()
        peers = {e["peer"] for e in doc["edges"]}
        assert len(doc["edges"]) == 5  # 4 tracked + the overflow bucket
        assert "other" in peers
        other = next(e for e in doc["edges"] if e["peer"] == "other")
        assert other["count"] == 6

    def test_stats_totals_ride_the_collector(self):
        from dmlc_tpu.obs.metrics import REGISTRY
        rpc.EDGES.observe("p", "get", 100.0, 60.0)
        rpc.EDGES.observe("p", "get", 200.0, 80.0, ok=False)
        snap = REGISTRY.snapshot()
        got = snap["collectors"]["rpc"]
        assert got["count"] == 2 and got["errors"] == 1
        assert got["attributed"] == 2
        assert got["client_us"] == pytest.approx(300.0)
        assert got["server_us"] == pytest.approx(140.0)
        assert got["residual_us"] == pytest.approx(160.0)


class TestRpcEndpoint:
    def test_get_rpc_serves_edge_table(self):
        from dmlc_tpu.obs.serve import StatusServer
        rpc.EDGES.observe("peer:1", "get", 123.0, 45.0)
        srv = StatusServer(port=0)
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/rpc") as resp:
                doc = json.load(resp)
        finally:
            srv.close()
        assert doc["schema"] == rpc.RPC_SCHEMA
        (edge,) = doc["edges"]
        assert (edge["peer"], edge["verb"]) == ("peer:1", "get")

    def test_scrape_is_a_traced_edge(self):
        """Satellite: every scrape poll is its own traced operation —
        a slow scrape shows as a flow-linked client/server pair with
        queue/handle/write phases on the server side."""
        from dmlc_tpu.obs.serve import StatusServer, scrape
        srv = StatusServer(port=0)
        rec = obs_trace.start()
        try:
            snap = scrape(srv.port)
            evs = _settle(rec, lambda es: _server_spans(es))
        finally:
            obs_trace.stop()
            srv.close()
        assert "counters" in snap
        (cl,) = _client_spans(evs, "scrape")
        sv = [e for e in _server_spans(evs)
              if e["args"][rpc.TRACE_FIELD] == cl["args"][rpc.TRACE_FIELD]]
        assert len(sv) == 1
        for phase in ("queue_us", "handle_us", "write_us"):
            assert phase in sv[0]["args"], phase
        assert cl["args"]["server_us"] == pytest.approx(
            sv[0]["args"]["handle_us"], abs=0.11)

    def test_gang_view_carries_rpc(self):
        """Satellite: GangAggregator polls surface each rank's edge
        totals under ranks.*.rpc (and the poll itself is traced)."""
        from dmlc_tpu.obs.aggregate import GangAggregator
        from dmlc_tpu.obs.serve import StatusServer
        rpc.EDGES.observe("peer:1", "get", 123.0, 45.0)
        srv = StatusServer(port=0)
        try:
            agg = GangAggregator(ports=[srv.port])
            agg.poll_once()
            view = agg.view()
        finally:
            srv.close()
        (rank,) = view["ranks"].values()
        assert rank["rpc"]["count"] >= 1


class TestEmulatorDecomposition:
    def test_server_handle_matches_modeled_delay(self, tmp_path):
        """Acceptance: the edge table decomposes client latency into
        server handle vs residual within ±20% of the emulator's
        modeled wire delay."""
        modeled_s = 0.02
        em = objstore.configure(root=str(tmp_path / "root"),
                                latency_s=modeled_s,
                                block_bytes=1 << 16)
        em.put("b", "k.bin", b"x" * (1 << 17))  # 2 blocks
        obs_trace.start()
        try:
            s = create_seek_stream_for_read("obj://b/k.bin")
            got = 0
            while True:
                chunk = s.read(1 << 20)
                if not chunk:
                    break
                got += len(chunk)
            s.close()
        finally:
            obs_trace.stop()
        assert got == 1 << 17
        edges = {(e["peer"], e["verb"]): e
                 for e in rpc.view()["edges"]}
        get = edges[("emulator", "get")]
        assert get["attributed"] == get["count"] >= 2
        modeled_us = modeled_s * 1e6
        assert get["server_us"]["p50"] == pytest.approx(
            modeled_us, rel=0.20)
        # the residual (client - server) is the non-modeled overhead:
        # far under the wire delay, so the decomposition is meaningful
        assert get["residual_us"]["p50"] < 0.2 * modeled_us
        # client ≈ server + residual by construction
        assert get["client_us"]["p50"] == pytest.approx(
            get["server_us"]["p50"] + get["residual_us"]["p50"],
            rel=0.25)


class TestChaosPerAttemptSpans:
    def test_injected_retries_are_countable_spans(self, tmp_path):
        """Satellite: a FaultPlan-injected retry at io.objstore.get
        produces one client span per ATTEMPT, all sharing the pinned
        trace_id — retries countable straight off the timeline."""
        em = objstore.configure(root=str(tmp_path / "root"))
        em.put("b", "k.bin", b"z" * (1 << 14))
        inject.install("site=io.objstore.get,fault=ioerror,times=2")
        rec = obs_trace.start()
        try:
            s = create_seek_stream_for_read("obj://b/k.bin")
            data = s.read(1 << 20)
            s.close()
        finally:
            obs_trace.stop()
            inject.uninstall()
        assert len(data) == 1 << 14
        spans = _client_spans(chrome_events(rec), "get")
        assert len(spans) == 3  # 2 injected failures + the success
        oks = sorted(e["args"]["ok"] for e in spans)
        assert oks == [False, False, True]
        tids = {e["args"][rpc.TRACE_FIELD].split("-")[0] for e in spans}
        assert len(tids) == 1, "attempts must share the trace_id"
        span_ids = {e["args"][rpc.TRACE_FIELD] for e in spans}
        assert len(span_ids) == 3, "each attempt is its own span"
        edge = next(e for e in rpc.view()["edges"]
                    if e["verb"] == "get")
        assert edge["errors"] == 2


class TestRendezvousTraced:
    def test_client_server_pair_over_the_line_protocol(self):
        from dmlc_tpu.rendezvous import RendezvousClient
        from dmlc_tpu.rendezvous.service import RendezvousService
        svc = RendezvousService(port=0)
        host, port = svc.address
        rec = obs_trace.start()
        try:
            c = RendezvousClient(host, port, gang="g", member="w0")
            assert c.join() == 0
            assert c.commit("p0", 10) is True
            c.leave()
            evs = _settle(rec, lambda es: len(_server_spans(es)) >= 3)
        finally:
            obs_trace.stop()
            svc.close()
        (commit,) = _client_spans(evs, "commit")
        assert commit["args"]["server_us"] is not None
        # the service handler recorded the paired server span (same
        # process here; the gang acceptance below proves cross-process)
        paired = [e for e in _server_spans(evs)
                  if e["args"][rpc.TRACE_FIELD]
                  == commit["args"][rpc.TRACE_FIELD]]
        assert len(paired) == 1
        assert paired[0]["args"]["handle_us"] == pytest.approx(
            commit["args"]["server_us"], abs=0.11)


class TestTracingOffOverhead:
    def test_off_overhead_smoke_under_2pct(self, tmp_path):
        """Tier-1 gate (PR 3 discipline): with tracing OFF the rpc
        seams cost one global read + branch per edge — judged against
        tracing ON on the quietest interleaved pair, the off epochs
        must stay within 2% (+ absolute slack for sub-100ms noise)."""
        em = objstore.configure(root=str(tmp_path / "root"),
                                block_bytes=1 << 20, hydrate=False)
        em.put("b", "big.bin", b"q" * (1 << 22))  # 4 x 1MiB GETs

        def epoch_wall():
            t0 = time.perf_counter()
            s = create_seek_stream_for_read("obj://b/big.bin")
            while s.read(1 << 20):
                pass
            s.close()
            return time.perf_counter() - t0

        epoch_wall()  # warm imports/caches outside the measurement
        off, on = [], []
        for _ in range(5):
            off.append(epoch_wall())
            obs_trace.start()
            try:
                on.append(epoch_wall())
            finally:
                obs_trace.stop()
        grace = 0.010 / min(off)  # flat 10 ms, scaled to the wall
        ratios = [a / b for a, b in zip(on, off)]
        assert min(ratios) <= 1.02 + grace, (on, off, ratios)


# ------------------------------------------------- THE gang acceptance

class TestGangTraceAcceptance:
    def test_two_rank_gang_merged_trace_is_flow_linked(self, tmp_path):
        """A REAL 2-process gang (tests/peer_worker.py, no jax) with
        tracing + rendezvous on: the merged timeline must contain at
        least one flow-linked client/server span pair for EVERY edge
        type — peer /pages (cross-process), objstore GET (emulator),
        and the rendezvous commit (worker -> launcher service)."""
        from dmlc_tpu.parallel.launch import launch_local

        payload = os.urandom(1 << 20)
        objroot = tmp_path / "objroot"
        em = objstore.configure(root=str(objroot))
        try:
            em.put("bench", "gang.bin", payload)
        finally:
            objstore.configure(None)
        worker = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "peer_worker.py")
        out_dir = tmp_path / "gang"
        out_dir.mkdir()
        trace_dir = tmp_path / "traces"
        env = {
            "DMLC_TPU_OBJSTORE_ROOT": str(objroot),
            "PYTHONPATH": os.pathsep.join(
                [os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__)))]
                + [p for p in os.environ.get(
                    "PYTHONPATH", "").split(os.pathsep) if p]),
        }
        # the rendezvous service runs in THIS process: record here so
        # its server spans merge with the workers' trace files
        rec = obs_trace.start()
        try:
            codes = launch_local(
                2, [sys.executable, worker, "obj://bench/gang.bin",
                    str(out_dir), str(1 << 16), "2"],
                env=env, serve_ports=True, trace_dir=str(trace_dir),
                rendezvous=True, timeout=180)
        finally:
            obs_trace.stop()
        assert codes[:2] == [0, 0]
        merged_path = trace_dir / "trace-gang.json"
        assert merged_path.exists()
        evs = json.load(open(merged_path))["traceEvents"]
        evs += chrome_events(rec)  # + the launcher's service spans

        clients = _client_spans(evs)
        servers = {e["args"][rpc.TRACE_FIELD]: e
                   for e in _server_spans(evs)}
        flow_ids = {(f["ph"], f["id"]) for f in evs
                    if f.get("name") == "rpc.flow"}

        def linked_pairs(verb, cross_process=False):
            pairs = []
            for cl in clients:
                if cl["args"]["verb"] != verb or not cl["args"]["ok"]:
                    continue
                sv = servers.get(cl["args"][rpc.TRACE_FIELD])
                if sv is None:
                    continue
                if cross_process and sv["pid"] == cl["pid"]:
                    continue
                tid = cl["args"][rpc.TRACE_FIELD].split("-")[0]
                if ("s", tid) in flow_ids and ("f", tid) in flow_ids:
                    pairs.append((cl, sv))
            return pairs

        # edge type 1: peer /pages — MUST cross process rows
        assert linked_pairs("pages", cross_process=True), \
            "no flow-linked cross-process peer /pages pair"
        # edge type 2: objstore GET (emulator models the server half)
        assert linked_pairs("get"), \
            "no flow-linked objstore GET pair"
        # edge type 3: rendezvous commit (server span lives in the
        # launcher's recorder; the service names the op it dispatched)
        assert linked_pairs("commit"), \
            "no flow-linked rendezvous commit pair"

        # and the edge table made it into each rank's bench output
        # plane: /rpc on a live rank was exercised by the scrape test;
        # here every rank's trace must carry BOTH span categories
        for r in (0, 1):
            rank_evs = json.load(
                open(trace_dir / f"trace-rank{r}.json"))["traceEvents"]
            cats = {e.get("cat") for e in rank_evs}
            assert "rpc.client" in cats, f"rank {r} recorded no clients"
