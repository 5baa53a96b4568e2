"""Lint gate wired into tier-1 (SURVEY §4's scripts/lint.py analogue):
the suite fails on a lint regression, with or without the optional
external tools installed."""

import os
import sys

import pytest

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "scripts"))
import lint  # noqa: E402


class TestLintGate:
    def test_builtin_python_lint_clean(self):
        findings = lint.builtin_lint(lint.python_files())
        assert findings == [], "\n".join(findings)

    def test_ruff_clean_when_available(self):
        findings = lint.run_ruff()
        if findings is None:
            pytest.skip("ruff not installed on this host")
        assert findings == [], "\n".join(findings)

    def test_clang_format_clean_when_available(self):
        findings = lint.run_clang_format()
        if findings is None:
            pytest.skip("clang-format not installed on this host")
        assert findings == [], "\n".join(findings)

    def test_builtin_catches_planted_violations(self, tmp_path):
        # the gate must actually bite: a tab-indented, trailing-space,
        # newline-less file yields one finding per violation class
        bad = tmp_path / "bad.py"
        bad.write_bytes(b"def f():\n\treturn 1 \nx = f()")
        findings = lint.builtin_lint([str(bad)])
        kinds = "\n".join(findings)
        assert "tab in indentation" in kinds
        assert "trailing whitespace" in kinds
        assert "missing trailing newline" in kinds

    def test_builtin_catches_syntax_error(self, tmp_path):
        bad = tmp_path / "broken.py"
        bad.write_text("def f(:\n    pass\n")
        findings = lint.builtin_lint([str(bad)])
        assert any("syntax error" in f for f in findings)

    def test_obs_gate_clean(self):
        # no bare print()/ad-hoc stats() surfaces crept into dmlc_tpu/
        # outside obs/ and the pinned pre-obs allowlists
        findings = lint.obs_lint(lint.python_files())
        assert findings == [], "\n".join(findings)

    def test_obs_gate_catches_planted_violations(self):
        # the gate must bite on package files outside the allowlists —
        # plant one in-memory via a real temp file under dmlc_tpu/
        bad = os.path.join(lint.REPO, "dmlc_tpu", "_lintprobe.py")
        with open(bad, "w") as f:
            f.write("def stats():\n    return {}\n\n\n"
                    "def run():\n    print('x')\n")
        try:
            findings = lint.obs_lint([bad])
        finally:
            os.remove(bad)
        kinds = "\n".join(findings)
        assert "bare print()" in kinds
        assert "new stats() surface" in kinds

    def test_metric_gate_clean(self):
        # every literal instrument name in dmlc_tpu/ is exposition-safe
        # and no module outside obs/serve.py stands up an http.server
        findings = lint.metric_lint(lint.python_files())
        assert findings == [], "\n".join(findings)

    def test_metric_gate_catches_planted_violations(self):
        bad = os.path.join(lint.REPO, "dmlc_tpu", "_lintprobe.py")
        with open(bad, "w") as f:
            f.write("from http.server import HTTPServer\n"
                    "from dmlc_tpu.obs.metrics import REGISTRY\n"
                    "REGISTRY.counter('Bad Name!').inc()\n"
                    "REGISTRY.gauge('ok.name').set(1)\n")
        try:
            findings = lint.metric_lint([bad])
        finally:
            os.remove(bad)
        kinds = "\n".join(findings)
        assert "metric name 'Bad Name!'" in kinds
        assert "http.server outside" in kinds
        assert "ok.name" not in kinds  # the clean name passes

    def test_metric_gate_allows_serve_module(self):
        serve = os.path.join(lint.REPO, "dmlc_tpu", "obs", "serve.py")
        assert lint.metric_lint([serve]) == []

    def test_resilience_gate_clean(self):
        # no hand-rolled sleep/retry loops or naked except-OSError-
        # continue outside dmlc_tpu/resilience/ and the pinned allowlist
        findings = lint.resilience_lint(lint.python_files())
        assert findings == [], "\n".join(findings)

    def test_resilience_gate_catches_planted_violations(self):
        bad = os.path.join(lint.REPO, "dmlc_tpu", "_lintprobe.py")
        with open(bad, "w") as f:
            f.write("import time\n"
                    "def pull(paths):\n"
                    "    for p in paths:\n"
                    "        try:\n"
                    "            return open(p)\n"
                    "        except OSError:\n"
                    "            continue\n"
                    "def fetch(fn):\n"
                    "    while True:\n"
                    "        try:\n"
                    "            return fn()\n"
                    "        except (IOError, ValueError):\n"
                    "            time.sleep(0.1)\n")
        try:
            findings = lint.resilience_lint([bad])
        finally:
            os.remove(bad)
        kinds = "\n".join(findings)
        assert "naked 'except OSError: continue'" in kinds
        assert "hand-rolled sleep/retry loop" in kinds

    def test_resilience_gate_exempts_resilience_package(self):
        # the policy engine itself sleeps between attempts, by design
        pol = os.path.join(lint.REPO, "dmlc_tpu", "resilience",
                           "policy.py")
        assert lint.resilience_lint([pol]) == []

    def test_io_seam_gate_clean(self):
        # no direct open()/os.stat on data paths in dmlc_tpu/ outside
        # dmlc_tpu/io/ and the pinned allowlist — byte access goes
        # through the FileSystem/stream seams so retry policies and
        # fault plans always apply
        findings = lint.io_seam_lint(lint.python_files())
        assert findings == [], "\n".join(findings)

    def test_io_seam_gate_catches_planted_violations(self):
        bad = os.path.join(lint.REPO, "dmlc_tpu", "_lintprobe2.py")
        with open(bad, "w") as f:
            f.write("import os\n"
                    "def load(p):\n"
                    "    with open(p, 'rb') as fh:\n"
                    "        return fh.read(), os.stat(p).st_size\n")
        try:
            findings = lint.io_seam_lint([bad])
        finally:
            os.remove(bad)
        kinds = "\n".join(findings)
        assert "direct open() outside dmlc_tpu/io/" in kinds
        assert "direct os.stat() outside dmlc_tpu/io/" in kinds

    def test_row_loop_gate_clean(self):
        # no per-row Python loops over block payloads crept into
        # dmlc_tpu/data/ or dmlc_tpu/pipeline/ outside the pinned
        # golden-path allowlist — per-row work is engine (ABI-5 padded
        # emission) or vectorized numpy (data.padding)
        findings = lint.row_loop_lint(lint.python_files())
        assert findings == [], "\n".join(findings)

    def test_row_loop_gate_catches_planted_violations(self):
        bad = os.path.join(lint.REPO, "dmlc_tpu", "data",
                           "_lintprobe3.py")
        with open(bad, "w") as f:
            f.write("def tally(block):\n"
                    "    s = 0.0\n"
                    "    for row in block:\n"
                    "        s += float(row.label)\n"
                    "    n = [block.label[i] "
                    "for i in range(block.size)]\n"
                    "    return s, n\n")
        try:
            findings = lint.row_loop_lint([bad])
        finally:
            os.remove(bad)
        assert len(findings) == 2, "\n".join(findings)
        assert all("per-row Python loop" in f for f in findings)

    def test_row_loop_gate_scope(self):
        # block-level loops are fine; rowblock.py's Row protocol and
        # files outside data//pipeline/ are exempt
        probe = os.path.join(lint.REPO, "dmlc_tpu", "data",
                             "_lintprobe3.py")
        with open(probe, "w") as f:
            f.write("def drain(parser):\n"
                    "    return [b.nnz for b in parser]\n")
        try:
            assert lint.row_loop_lint([probe]) == []
        finally:
            os.remove(probe)
        rb = os.path.join(lint.REPO, "dmlc_tpu", "data", "rowblock.py")
        assert lint.row_loop_lint([rb]) == []  # pinned golden path
        outside = os.path.join(lint.REPO, "dmlc_tpu", "parallel",
                               "sharded.py")
        assert lint.row_loop_lint([outside]) == []  # out of scope

    def test_verdict_gate_clean(self):
        # the analysis-verdict key set matches the pin everywhere a
        # literal verdict dict appears (dmlc_tpu/ + scripts/) and
        # obs/analyze.py's VERDICT_KEYS tuple equals it
        findings = lint.verdict_lint(lint.python_files())
        assert findings == [], "\n".join(findings)

    def test_verdict_gate_pin_matches_analyze(self):
        # the two sources of truth agree (change both consciously)
        from dmlc_tpu.obs.analyze import VERDICT_KEYS
        assert tuple(VERDICT_KEYS) == tuple(lint.VERDICT_KEYS)

    def test_verdict_gate_catches_planted_violations(self):
        bad = os.path.join(lint.REPO, "dmlc_tpu", "_lintprobe4.py")
        with open(bad, "w") as f:
            f.write("def fake():\n"
                    "    return {'bound': 'parse', 'evidence': [],\n"
                    "            'extra_key': 1}\n")
        try:
            findings = lint.verdict_lint([bad])
        finally:
            os.remove(bad)
        assert len(findings) == 1, "\n".join(findings)
        assert "verdict-shaped dict" in findings[0]

    def test_verdict_gate_scans_scripts_too(self):
        bad = os.path.join(lint.REPO, "scripts", "_lintprobe5.py")
        with open(bad, "w") as f:
            f.write("V = {'bound': 'xfer', 'evidence': []}\n")
        try:
            findings = lint.verdict_lint([bad])
        finally:
            os.remove(bad)
        assert len(findings) == 1 and "obsctl" not in findings[0]

    def test_verdict_gate_requires_pin_in_analyze(self, tmp_path):
        # a drifted VERDICT_KEYS tuple in analyze.py is a finding —
        # simulate by linting a fake tree rooted at the analyze path
        import ast as _ast
        fake = ("VERDICT_KEYS = ('schema', 'bound')\n")
        tree = _ast.parse(fake)
        findings = []
        probe = os.path.join(lint.REPO, "dmlc_tpu", "obs", "analyze.py")
        findings = lint.verdict_lint(
            [probe], trees={probe: (lint._ANALYZE_REL, tree)})
        assert any("drifted from the lint pin" in f for f in findings)

    def test_io_seam_gate_exempts_io_package_and_allowlist(self):
        fsys = os.path.join(lint.REPO, "dmlc_tpu", "io", "filesys.py")
        assert lint.io_seam_lint([fsys]) == []
        flight = os.path.join(lint.REPO, "dmlc_tpu", "obs", "flight.py")
        assert lint.io_seam_lint([flight]) == []

    def test_knob_gate_clean(self):
        # steady-state knob mutation (set_capacity, depth/window
        # assignment, configure(coalesce/parallel/codec_level))
        # confined to the exploration rails + the pinned allowlist
        findings = lint.knob_lint(lint.python_files())
        assert findings == [], "\n".join(findings)

    def test_knob_gate_catches_planted_violations(self):
        bad = os.path.join(lint.REPO, "dmlc_tpu", "_lintprobe10.py")
        with open(bad, "w") as f:
            f.write("def tune(ti, it, dev, objstore, a, b):\n"
                    "    ti.set_capacity(8)\n"
                    "    it.prefetch_depth = 4\n"
                    "    dev.window = 16\n"
                    "    dev.window += 8\n"          # augmented form
                    "    dev.window: int = 4\n"      # annotated form
                    "    a.prefetch_depth, b = 2, 0\n"  # tuple unpack
                    "    objstore.configure(coalesce=8, parallel=2)\n"
                    "    objstore.configure(hydrate=False)\n"  # fine
                    "    b[dev.window] = 1\n"        # READ: fine
                    "    dev.window.inner = 2\n")    # assigns .inner
        try:
            findings = lint.knob_lint([bad])
        finally:
            os.remove(bad)
        kinds = "\n".join(findings)
        assert len(findings) == 7, kinds
        assert "direct set_capacity()" in kinds
        assert kinds.count(".prefetch_depth assignment") == 2
        assert kinds.count(".window assignment") == 3
        assert "configure(coalesce/parallel=...)" in kinds

    def test_knob_gate_exempts_the_rails(self):
        for rel in ("pipeline/autotune.py", "obs/control.py",
                    "pipeline/graph.py"):
            path = os.path.join(lint.REPO, "dmlc_tpu",
                                *rel.split("/"))
            assert lint.knob_lint([path]) == [], rel

    def test_verdict_gate_exempts_decision_records(self):
        # a control-plane ledger record carries bound+evidence but
        # CITES a verdict (by id) rather than being one — "outcome"
        # marks it; its shape is pinned by obs/control.py RECORD_KEYS
        probe = os.path.join(lint.REPO, "dmlc_tpu", "_lintprobe11.py")
        with open(probe, "w") as f:
            f.write("R = {'bound': 'parse', 'evidence': [],\n"
                    "     'outcome': 'trial'}\n")
        try:
            assert lint.verdict_lint([probe]) == []
        finally:
            os.remove(probe)

    def test_codec_gate_clean(self):
        # no direct zlib/gzip/bz2/lzma imports in dmlc_tpu/ outside
        # io/codec.py and the pinned crc32 allowlist
        findings = lint.codec_lint(lint.python_files())
        assert findings == [], "\n".join(findings)

    def test_codec_gate_catches_planted_violations(self):
        bad = os.path.join(lint.REPO, "dmlc_tpu", "_lintprobe6.py")
        with open(bad, "w") as f:
            f.write("import zlib\nfrom gzip import compress\n")
        try:
            findings = lint.codec_lint([bad])
        finally:
            os.remove(bad)
        assert len(findings) == 2, "\n".join(findings)
        assert all("io/codec.py" in f for f in findings)

    def test_codec_gate_exempts_codec_and_crc_allowlist(self):
        codec = os.path.join(lint.REPO, "dmlc_tpu", "io", "codec.py")
        assert lint.codec_lint([codec]) == []
        # resilience/policy.py's zlib.crc32 use is pinned — but a gzip
        # import there would NOT be covered by the crc pin
        policy = os.path.join(lint.REPO, "dmlc_tpu", "resilience",
                              "policy.py")
        assert lint.codec_lint([policy]) == []

    def test_arrow_gate_clean(self):
        # pyarrow imports in dmlc_tpu/ confined to the parquet golden
        # (the native lane must never silently lean on pyarrow)
        findings = lint.arrow_lint(lint.python_files())
        assert findings == [], "\n".join(findings)

    def test_arrow_gate_catches_planted_violation(self):
        bad = os.path.join(lint.REPO, "dmlc_tpu", "_lintprobe12.py")
        with open(bad, "w") as f:
            f.write("import pyarrow\nfrom pyarrow import parquet\n")
        try:
            findings = lint.arrow_lint([bad])
        finally:
            os.remove(bad)
        assert len(findings) == 2, "\n".join(findings)
        assert all("parquet_parser.py" in f for f in findings)

    def test_arrow_gate_exempts_golden_and_bench(self):
        golden = os.path.join(lint.REPO, "dmlc_tpu", "data",
                              "parquet_parser.py")
        assert lint.arrow_lint([golden]) == []

    def test_profile_gate_clean(self):
        # sys._current_frames walks and cProfile/profile/pstats
        # imports confined to obs/profile.py
        findings = lint.profile_lint(lint.python_files())
        assert findings == [], "\n".join(findings)

    def test_profile_gate_catches_planted_violations(self):
        bad = os.path.join(lint.REPO, "dmlc_tpu", "_lintprobe7.py")
        with open(bad, "w") as f:
            f.write("import sys\nimport cProfile\n"
                    "from sys import _current_frames\n"
                    "frames = sys._current_frames()\n")
        try:
            findings = lint.profile_lint([bad])
        finally:
            os.remove(bad)
        # cProfile import + BOTH _current_frames forms (attribute
        # access and the from-import bypass)
        assert len(findings) == 3, "\n".join(findings)
        assert all("obs/profile.py" in f for f in findings)

    def test_profile_gate_exempts_profile_module_and_pkg_import(self):
        mod = os.path.join(lint.REPO, "dmlc_tpu", "obs", "profile.py")
        assert lint.profile_lint([mod]) == []
        # `from dmlc_tpu.obs import profile` must NOT trip the
        # stdlib-`profile` import check — only top-level module
        # imports count
        probe = os.path.join(lint.REPO, "dmlc_tpu", "_lintprobe8.py")
        with open(probe, "w") as f:
            f.write("from dmlc_tpu.obs import profile as _prof\n"
                    "from dmlc_tpu.obs.profile import hot_frames\n")
        try:
            findings = lint.profile_lint([probe])
        finally:
            os.remove(probe)
        assert findings == [], "\n".join(findings)

    def test_http_client_gate_clean(self):
        # http.client/urllib.request imports in dmlc_tpu/ confined to
        # the objstore client modules + obs/serve.py's scrape
        findings = lint.http_client_lint(lint.python_files())
        assert findings == [], "\n".join(findings)

    def test_http_client_gate_catches_planted_violations(self):
        bad = os.path.join(lint.REPO, "dmlc_tpu", "_lintprobe9.py")
        with open(bad, "w") as f:
            f.write("import urllib.request\n"
                    "import http.client\n"
                    "from urllib.request import urlopen\n"
                    "from http import client\n"
                    "from urllib.parse import urlparse\n")  # fine
        try:
            findings = lint.http_client_lint([bad])
        finally:
            os.remove(bad)
        assert len(findings) == 4, "\n".join(findings)
        assert all("objstore client modules" in f for f in findings)

    def test_socket_gate_clean(self):
        # raw socket/socketserver imports in dmlc_tpu/ confined to
        # rendezvous/service.py + obs/serve.py (the rendezvous wire
        # protocol and the HTTP status plane)
        findings = lint.socket_lint(lint.python_files())
        assert findings == [], "\n".join(findings)

    def test_socket_gate_catches_planted_violations(self):
        bad = os.path.join(lint.REPO, "dmlc_tpu", "_lintprobe12.py")
        with open(bad, "w") as f:
            f.write("import socket\n"
                    "import socketserver\n"
                    "from socket import create_connection\n"
                    "from urllib.parse import urlparse\n")  # fine
        try:
            findings = lint.socket_lint([bad])
        finally:
            os.remove(bad)
        assert len(findings) == 3, "\n".join(findings)
        assert all("rendezvous/service.py" in f for f in findings)

    def test_socket_gate_allows_service_and_serve(self):
        for rel in ("rendezvous/service.py", "obs/serve.py"):
            path = os.path.join(lint.REPO, "dmlc_tpu",
                                *rel.split("/"))
            assert lint.socket_lint([path]) == [], rel

    def test_thread_gate_clean(self):
        # threading.Thread / executor pools in dmlc_tpu/pipeline/
        # confined to scheduler.py (the budget owner)
        findings = lint.thread_lint(lint.python_files())
        assert findings == [], "\n".join(findings)

    def test_thread_gate_catches_planted_violations(self):
        bad = os.path.join(lint.REPO, "dmlc_tpu", "pipeline",
                           "_lintprobe10.py")
        with open(bad, "w") as f:
            f.write("import threading\n"
                    "from threading import Thread\n"
                    "from concurrent.futures import "
                    "ThreadPoolExecutor\n"
                    "t = threading.Thread(target=print)\n"
                    "u = Thread(target=print)\n"
                    "p = ThreadPoolExecutor(2)\n"
                    "ok = threading.Lock()\n")  # locks are fine
        try:
            findings = lint.thread_lint([bad])
        finally:
            os.remove(bad)
        assert len(findings) == 3, "\n".join(findings)
        assert all("scheduler-owned budget" in f for f in findings)

    def test_thread_gate_scope_and_allowlist(self):
        # the scheduler module itself and code OUTSIDE pipeline/ are
        # exempt (ThreadedIter et al. are the audited seams)
        sched = os.path.join(lint.REPO, "dmlc_tpu", "pipeline",
                             "scheduler.py")
        assert lint.thread_lint([sched]) == []
        outside = os.path.join(lint.REPO, "dmlc_tpu", "_lintprobe11.py")
        with open(outside, "w") as f:
            f.write("import threading\n"
                    "t = threading.Thread(target=print)\n")
        try:
            assert lint.thread_lint([outside]) == []
        finally:
            os.remove(outside)

    def test_http_client_gate_allows_client_modules(self):
        for rel in ("io/objstore/http_client.py", "io/objstore/peer.py",
                    "obs/serve.py"):
            path = os.path.join(lint.REPO, "dmlc_tpu",
                                *rel.split("/"))
            assert lint.http_client_lint([path]) == [], rel

    def test_trace_header_gate_clean(self):
        # the X-Dmlc-Trace/X-Dmlc-Handle-Us wire literals live only in
        # obs/rpc.py; everything else imports the helpers
        findings = lint.trace_header_lint(lint.python_files())
        assert findings == [], "\n".join(findings)

    def test_trace_header_gate_catches_planted_violations(self):
        bad = os.path.join(lint.REPO, "dmlc_tpu", "_lintprobe13.py")
        with open(bad, "w") as f:
            f.write("H = 'X-Dmlc-Trace'\n"
                    "def f(resp):\n"
                    "    return resp.headers.get('X-Dmlc-Handle-Us')\n"
                    "OK = 'X-Dmlc-Codec'\n")  # other headers are fine
        try:
            findings = lint.trace_header_lint([bad])
        finally:
            os.remove(bad)
        assert len(findings) == 2, "\n".join(findings)
        assert all("obs/rpc.py" in f for f in findings)

    def test_trace_header_gate_allows_rpc_module(self):
        path = os.path.join(lint.REPO, "dmlc_tpu", "obs", "rpc.py")
        assert lint.trace_header_lint([path]) == []

    def test_slo_gate_clean(self):
        # slo.* instrument names and the 14.4/6.0 burn-rate floats
        # live only in obs/slo.py; everyone else reads the engine
        findings = lint.slo_lint(lint.python_files())
        assert findings == [], "\n".join(findings)

    def test_slo_gate_catches_planted_violations(self):
        bad = os.path.join(lint.REPO, "dmlc_tpu", "_lintprobe14.py")
        with open(bad, "w") as f:
            f.write("def f(reg, name):\n"
                    "    reg.gauge('slo.x.attainment').set(1)\n"
                    "    reg.counter(f'slo.{name}.hits').inc()\n"
                    "    fast = 14.4\n"
                    "    slow = 6.0\n"
                    "    ok = reg.gauge('slow.x')\n"  # not slo.*
                    "    s = 'slo.free.string'\n"     # not an
                    "    return fast, slow, ok, s\n")  # instrument
        try:
            findings = lint.slo_lint([bad])
        finally:
            os.remove(bad)
        assert len(findings) == 4, "\n".join(findings)
        assert all("obs/slo.py" in f for f in findings)

    def test_slo_gate_allowlist_and_burn_exemption(self):
        slo = os.path.join(lint.REPO, "dmlc_tpu", "obs", "slo.py")
        assert lint.slo_lint([slo]) == []
        # supervise.py's 6.0 is a drain margin, not a burn threshold
        sup = os.path.join(lint.REPO, "dmlc_tpu", "resilience",
                           "supervise.py")
        assert lint.slo_lint([sup]) == []

    def test_random_gate_clean(self):
        # random/numpy.random construction in dmlc_tpu/io/ +
        # dmlc_tpu/data/ confined to dmlc_tpu/shuffle/ (epoch_rng)
        findings = lint.random_lint(lint.python_files())
        assert findings == [], "\n".join(findings)

    def test_random_gate_catches_planted_violations(self):
        bad = os.path.join(lint.REPO, "dmlc_tpu", "io",
                           "_lintprobe15.py")
        with open(bad, "w") as f:
            f.write("import random\n"
                    "import numpy.random\n"
                    "from random import shuffle\n"
                    "from numpy.random import RandomState\n"
                    "from numpy import random\n"
                    "import numpy as np\n"
                    "r = np.random.RandomState(0)\n"
                    "from dmlc_tpu.shuffle.permutation "
                    "import epoch_rng\n")  # fine: the one home
        try:
            findings = lint.random_lint([bad])
        finally:
            os.remove(bad)
        assert len(findings) == 6, "\n".join(findings)
        assert all("epoch_rng" in f for f in findings)

    def test_random_gate_scope(self):
        # outside io/ + data/ the gate does not apply (shuffle/ owns
        # the permutation; bench/test helpers keep their own rngs)
        out = os.path.join(lint.REPO, "dmlc_tpu", "_lintprobe16.py")
        with open(out, "w") as f:
            f.write("import random\nimport numpy.random\n")
        try:
            assert lint.random_lint([out]) == []
        finally:
            os.remove(out)
        # the permutation module itself draws numpy randomness freely
        perm = os.path.join(lint.REPO, "dmlc_tpu", "shuffle",
                            "permutation.py")
        assert lint.random_lint([perm]) == []
