#!/usr/bin/env python
"""Repo lint gate (SURVEY §4's scripts/lint.py analogue).

Two layers, so the gate degrades instead of disappearing on hosts
without the tools baked in:

- **Built-in checks** (always run, stdlib only): every tracked .py file
  must parse (ast), use spaces-only indentation, carry no trailing
  whitespace, no CR line endings, and end with exactly one newline.
- **Observability gate** (always run, AST-based): inside the
  ``dmlc_tpu`` package, bare ``print(`` calls and new ad-hoc ``def
  stats(`` dict surfaces are forbidden outside ``dmlc_tpu/obs/`` — new
  telemetry registers into ``dmlc_tpu.obs.metrics`` and logs through
  ``dmlc_tpu.obs.log``. Pre-obs surfaces are pinned in an allowlist;
  the list shrinks, it does not grow.
- **Metric-name gate** (always run, AST-based): every literal
  instrument name passed to ``.counter("...")``/``.gauge("...")``/
  ``.histogram("...")`` inside ``dmlc_tpu/`` must match
  ``[a-z0-9_.]+`` — anything else renders badly (or not at all) in
  the Prometheus exposition that ``obs/serve.py`` derives from the
  registry. And ``http.server`` may be used ONLY by ``obs/serve.py``:
  one status server per process, not one per module.
- **Resilience gate** (always run, AST-based): inside ``dmlc_tpu``,
  outside ``dmlc_tpu/resilience/``, hand-rolled retry loops (a loop
  whose body both sleeps and swallows OSError-family exceptions) and
  naked ``except OSError: continue`` handlers are forbidden — retries
  are policy (``dmlc_tpu.resilience.RetryPolicy`` via ``guarded()``),
  not ad-hoc control flow. The two pre-resilience skip-not-retry
  handlers are pinned in an allowlist; the list shrinks, it does not
  grow.
- **Verdict-schema gate** (always run, AST-based): the analysis
  verdict's key set (``dmlc_tpu/obs/analyze.py`` ``VERDICT_KEYS``) is
  pinned here, and any literal dict that claims to be a verdict
  (``"bound"`` + ``"evidence"`` keys) anywhere in ``dmlc_tpu/`` or
  ``scripts/`` must match it exactly — the ``/analyze`` endpoint,
  the ``"analysis"`` blocks of archived BENCH JSON files, and
  ``scripts/obsctl.py`` can never drift apart.
- **Knob gate** (always run, AST-based): steady-state knob mutation —
  ``.set_capacity()`` calls, ``.prefetch_depth``/``.window`` attribute
  assignment, ``objstore.configure()`` with coalesce/parallel/
  codec_level — is confined to the exploration rails
  (``pipeline/autotune.py`` + ``obs/control.py``) plus the pinned
  modules that DEFINE the knobs, so every knob move lands in the
  control plane's decision ledger with the evidence that caused it.
- **Codec gate** (always run, AST-based): direct ``zlib``/``gzip``/
  ``bz2``/``lzma`` imports inside ``dmlc_tpu/`` are forbidden outside
  ``io/codec.py`` (the one compressed-page seam; the pinned exception:
  ``resilience/policy.py``'s ``zlib.crc32`` jitter hash) — page bytes
  compress through one self-describing frame, never ad-hoc streams.
- **Profile gate** (always run, AST-based): ``sys._current_frames``
  walks and ``cProfile``/``profile``/``pstats`` imports inside
  ``dmlc_tpu/`` are confined to ``obs/profile.py`` — the process has
  ONE sampling profiler (one trie, one budget, one /profile payload);
  a second frame-walker elsewhere would mint a parallel universe the
  watchdog, flight bundles and ``hot_frames`` evidence never see.
- **Http-client gate** (always run, AST-based): ``http.client`` and
  ``urllib.request`` imports inside ``dmlc_tpu/`` are confined to the
  objstore client modules (``io/objstore/http_client.py``,
  ``io/objstore/peer.py``) and ``obs/serve.py``'s scrape — outbound
  HTTP elsewhere would bypass the ``io.objstore.*``/``obs.scrape``
  retry seams, fault plans, and byte counters (the ``http.server``
  side is pinned to ``obs/serve.py`` by the metric gate).
- **SLO gate** (always run, AST-based): instruments named in the
  ``slo.*`` family and the burn-rate threshold floats (14.4 / 6.0)
  are confined to ``obs/slo.py`` — one home for the alert math; every
  other surface imports ``FAST_BURN_RATE``/``SLOW_BURN_RATE`` and
  lets the engine export the per-objective gauges (the pinned
  exception: ``resilience/supervise.py``'s ``6.0`` teardown drain
  margin).
- **Random gate** (always run, AST-based): ``random`` /
  ``numpy.random`` construction inside ``dmlc_tpu/io/`` and
  ``dmlc_tpu/data/`` is forbidden — seeded-permutation ownership has
  one home (``dmlc_tpu/shuffle/``): epoch randomness is drawn from
  ``dmlc_tpu.shuffle.permutation.epoch_rng`` so the determinism
  contract (same seed ⇒ same order, restart-stable resume) holds.
- **Steady-path gate** (always run, AST-based): inside
  ``dmlc_tpu/data/`` and ``dmlc_tpu/pipeline/``, per-row Python loops
  over block payloads (``for row in …`` or ``range(<x>.size)`` index
  loops) are forbidden outside the pinned golden-path allowlist — the
  per-row work belongs to the engine's ABI-5 padded emission
  (``dtp_parser_next_padded``) or the vectorized ``data.padding`` ops.
- **ruff** over the Python tree and **clang-format --dry-run -Werror**
  over native/src/ — run when the binaries are importable/installed,
  reported as skipped otherwise.

Wired into the pytest suite via tests/test_lint.py, so tier-1 fails on
a lint regression. CLI: ``python scripts/lint.py`` exits 0 clean / 1
with findings on stderr.
"""

from __future__ import annotations

import ast
import os
import re
import subprocess
import sys
from typing import List, Optional

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SKIP_DIRS = {".git", ".claude", "__pycache__", ".pytest_cache", "build"}
NATIVE_SRC = os.path.join(REPO, "dmlc_tpu", "native", "src")


def python_files(root: str = REPO) -> List[str]:
    out = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames if d not in SKIP_DIRS]
        for name in filenames:
            if name.endswith(".py"):
                out.append(os.path.join(dirpath, name))
    return sorted(out)


def native_files(root: str = NATIVE_SRC) -> List[str]:
    if not os.path.isdir(root):
        return []
    return sorted(os.path.join(root, n) for n in os.listdir(root)
                  if n.endswith((".cc", ".h", ".cpp", ".hpp")))


def builtin_lint(paths: List[str]) -> List[str]:
    """Stdlib-only findings: ["path:line: message"]."""
    findings: List[str] = []
    for path in paths:
        rel = os.path.relpath(path, REPO)
        try:
            with open(path, "rb") as f:
                raw = f.read()
        except OSError as e:
            findings.append(f"{rel}:0: unreadable ({e})")
            continue
        if b"\r" in raw:
            findings.append(f"{rel}:0: CR line endings")
        try:
            text = raw.decode("utf-8")
        except UnicodeDecodeError as e:
            findings.append(f"{rel}:0: not UTF-8 ({e})")
            continue
        try:
            ast.parse(text, filename=rel)
        except SyntaxError as e:
            findings.append(f"{rel}:{e.lineno}: syntax error: {e.msg}")
            continue
        if text and not text.endswith("\n"):
            findings.append(f"{rel}:0: missing trailing newline")
        if text.endswith("\n\n"):
            findings.append(f"{rel}:0: trailing blank lines at EOF")
        for i, line in enumerate(text.split("\n"), 1):
            stripped = line.rstrip("\n")
            if stripped != stripped.rstrip():
                findings.append(f"{rel}:{i}: trailing whitespace")
            indent = stripped[:len(stripped) - len(stripped.lstrip())]
            if "\t" in indent:
                findings.append(f"{rel}:{i}: tab in indentation")
    return findings


# pre-obs surfaces, pinned (package-relative paths). print(): the build
# script, whose stdout IS its interface. stats(): the five shapes that now REGISTER into
# dmlc_tpu.obs.metrics (the methods stay for their callers). New code
# uses obs.metrics / obs.log instead of growing this list.
PRINT_ALLOWED = {
    "dmlc_tpu/native/build.py",
}
STATS_ALLOWED = {
    "dmlc_tpu/data/threaded_iter.py",
    "dmlc_tpu/native/bindings.py",
    "dmlc_tpu/pipeline/graph.py",
    "dmlc_tpu/utils/memory.py",
}


def _parse_package_trees(paths: List[str]) -> dict:
    """{path: (rel, ast)} for the dmlc_tpu/ files — parsed ONCE and
    shared by every AST gate (each gate re-parsing the tree tripled
    the lint cost per added gate). Unparseable files are absent;
    builtin_lint reports those."""
    trees = {}
    for path in paths:
        rel = os.path.relpath(path, REPO).replace(os.sep, "/")
        if not rel.startswith("dmlc_tpu/"):
            continue
        try:
            with open(path, "r", encoding="utf-8") as f:
                trees[path] = (rel, ast.parse(f.read(), filename=rel))
        except (OSError, SyntaxError, UnicodeDecodeError):
            pass
    return trees


def obs_lint(paths: List[str], trees: Optional[dict] = None) -> List[str]:
    """The observability gate: no new bare print()/ad-hoc stats() dict
    shapes inside dmlc_tpu/ outside obs/ (see module docstring)."""
    if trees is None:
        trees = _parse_package_trees(paths)
    findings: List[str] = []
    for path in paths:
        if path not in trees:
            continue
        rel, tree = trees[path]
        if rel.startswith("dmlc_tpu/obs/"):
            continue
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)
                    and node.func.id == "print"
                    and rel not in PRINT_ALLOWED):
                findings.append(
                    f"{rel}:{node.lineno}: bare print() in package code "
                    "— log through dmlc_tpu.obs.log / utils.logging")
            if (isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
                    and node.name == "stats"
                    and rel not in STATS_ALLOWED):
                findings.append(
                    f"{rel}:{node.lineno}: new stats() surface — "
                    "register a collector with dmlc_tpu.obs.metrics."
                    "REGISTRY instead of inventing a dict shape")
    return findings


# registry instrument names must survive the Prometheus name mangling
# in obs/serve.py losslessly: lowercase words joined by '.' (or '_')
METRIC_NAME_RE = re.compile(r"^[a-z0-9_.]+$")
# the ONE module allowed to stand up an HTTP server (package-relative)
HTTP_SERVER_ALLOWED = {"dmlc_tpu/obs/serve.py"}
_INSTRUMENT_METHODS = ("counter", "gauge", "histogram")


def metric_lint(paths: List[str],
                trees: Optional[dict] = None) -> List[str]:
    """The metric-name + http.server gate (see module docstring).
    Literal names only: f-string/dynamic names are built from literal
    parts that the regex already vets at their other call sites."""
    if trees is None:
        trees = _parse_package_trees(paths)
    findings: List[str] = []
    for path in paths:
        if path not in trees:
            continue
        rel, tree = trees[path]
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in _INSTRUMENT_METHODS
                    and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)):
                name = node.args[0].value
                if not METRIC_NAME_RE.match(name):
                    findings.append(
                        f"{rel}:{node.lineno}: metric name {name!r} — "
                        "registry instrument names must match "
                        "[a-z0-9_.]+ (Prometheus exposition contract, "
                        "obs/serve.py)")
            if rel in HTTP_SERVER_ALLOWED:
                continue
            if (isinstance(node, ast.Import)
                    and any(a.name == "http.server"
                            for a in node.names)) or \
               (isinstance(node, ast.ImportFrom)
                    and (node.module == "http.server"
                         or (node.module == "http"
                             and any(a.name == "server"
                                     for a in node.names)))):
                findings.append(
                    f"{rel}:{node.lineno}: http.server outside "
                    "obs/serve.py — the process status server lives "
                    "there (serve()/serve_if_env()), one per process")
    return findings


# Byte access goes through the FileSystem/stream seams in dmlc_tpu/io/
# — that is where retry policies and fault plans apply (guarded() at
# io.stream.*, io.filesys.*, io.objstore.*) and where the unified page
# store stamps/accounts bytes. A direct open()/os.stat on a data path
# elsewhere in the package silently bypasses all of it. The pinned
# exceptions are files whose bytes are NOT data-path bytes: telemetry
# output (trace exports, flight bundles, stall reports), archived
# result JSON, launcher log capture, and the config file.
# The list shrinks, it does not grow.
IO_SEAM_ALLOWED = {
    "dmlc_tpu/native/build.py",      # build tooling (zlib link probe)
    "dmlc_tpu/obs/analyze.py",       # BENCH result JSON (compare)
    "dmlc_tpu/obs/export.py",        # trace JSON export
    "dmlc_tpu/obs/flight.py",        # crash flight bundles
    "dmlc_tpu/obs/watchdog.py",      # stall reports
    "dmlc_tpu/parallel/launch.py",   # per-rank log capture
    "dmlc_tpu/utils/config.py",      # config file loader
}


def io_seam_lint(paths: List[str],
                 trees: Optional[dict] = None) -> List[str]:
    """The io-seam gate: no direct ``open()`` / ``os.stat()`` calls in
    dmlc_tpu/ outside dmlc_tpu/io/ (see IO_SEAM_ALLOWED)."""
    if trees is None:
        trees = _parse_package_trees(paths)
    findings: List[str] = []
    for path in paths:
        if path not in trees:
            continue
        rel, tree = trees[path]
        if rel.startswith("dmlc_tpu/io/") or rel in IO_SEAM_ALLOWED:
            continue
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if isinstance(f, ast.Name) and f.id == "open":
                findings.append(
                    f"{rel}:{node.lineno}: direct open() outside "
                    "dmlc_tpu/io/ — byte access goes through "
                    "create_stream/FileSystem (or PageStore) so retry "
                    "policies and fault plans apply")
            elif (isinstance(f, ast.Attribute) and f.attr == "stat"
                    and isinstance(f.value, ast.Name)
                    and f.value.id in ("os", "_os")):
                findings.append(
                    f"{rel}:{node.lineno}: direct os.stat() outside "
                    "dmlc_tpu/io/ — stat through "
                    "io.pagestore.stat_uri / FileSystem.get_path_info "
                    "so remote schemes and fault plans apply")
    return findings


# Compression is a SEAM (dmlc_tpu/io/codec.py: one self-describing
# page frame, one level contract, one corruption story the retry seams
# rely on), not a per-call-site choice: a direct zlib/gzip/bz2/lzma
# import elsewhere in the package would mint a second on-disk/on-wire
# byte format the sweep, the sidecar stamps, and the chaos tests never
# see. The one pinned exception is resilience/policy.py's zlib.crc32 —
# a deterministic jitter HASH, not compression. The list shrinks, it
# does not grow.
CODEC_ALLOWED = {"dmlc_tpu/io/codec.py"}
CODEC_CRC_ALLOWED = {"dmlc_tpu/resilience/policy.py"}
_CODEC_MODULES = {"zlib", "gzip", "bz2", "lzma"}


def codec_lint(paths: List[str],
               trees: Optional[dict] = None) -> List[str]:
    """The codec gate: no direct compression-module imports in
    dmlc_tpu/ outside io/codec.py (see above)."""
    if trees is None:
        trees = _parse_package_trees(paths)
    findings: List[str] = []
    for path in paths:
        if path not in trees:
            continue
        rel, tree = trees[path]
        if rel in CODEC_ALLOWED:
            continue
        for node in ast.walk(tree):
            mods = []
            if isinstance(node, ast.Import):
                mods = [a.name.split(".")[0] for a in node.names]
            elif isinstance(node, ast.ImportFrom) and node.module:
                mods = [node.module.split(".")[0]]
            hit = sorted(set(mods) & _CODEC_MODULES)
            if not hit:
                continue
            if rel in CODEC_CRC_ALLOWED and hit == ["zlib"]:
                continue  # the pinned crc32 jitter-hash use
            findings.append(
                f"{rel}:{node.lineno}: direct {'/'.join(hit)} import "
                "outside io/codec.py — page bytes compress through "
                "dmlc_tpu.io.codec (encode_page/decode_page) so the "
                "frame header, sidecar stamps and corruption handling "
                "stay one contract")
    return findings


# pyarrow is a BOUNDARY, not a dependency (ABI 8): the native engine
# decodes parquet pages itself, and the only package code allowed to
# lean on pyarrow is the frozen golden (data/parquet_parser.py — the
# byte-parity reference and the engine="auto" fallback). A pyarrow
# import anywhere else would
# silently re-introduce the Python-bound decode wall the native lane
# exists to remove — and break the package on hosts without pyarrow.
# The list shrinks, it does not grow.
ARROW_ALLOWED = {"dmlc_tpu/data/parquet_parser.py"}
_ARROW_MODULES = {"pyarrow"}


def arrow_lint(paths: List[str],
               trees: Optional[dict] = None) -> List[str]:
    """The pyarrow gate: imports confined to the parquet golden (see
    above)."""
    if trees is None:
        trees = _parse_package_trees(paths)
    findings: List[str] = []
    for path in paths:
        if path not in trees:
            continue
        rel, tree = trees[path]
        if rel in ARROW_ALLOWED:
            continue
        for node in ast.walk(tree):
            mods = []
            if isinstance(node, ast.Import):
                mods = [a.name.split(".")[0] for a in node.names]
            elif isinstance(node, ast.ImportFrom) and node.module \
                    and node.level == 0:
                mods = [node.module.split(".")[0]]
            hit = sorted(set(mods) & _ARROW_MODULES)
            if hit:
                findings.append(
                    f"{rel}:{node.lineno}: pyarrow import outside "
                    "data/parquet_parser.py — parquet decode goes "
                    "through the parser registry (format "
                    "'parquet_native': the ABI-8 native page decoder, "
                    "pyarrow-golden fallback), never an ad-hoc arrow "
                    "boundary")
    return findings


# Sampling/profiling is a SEAM (dmlc_tpu/obs/profile.py: one sampler
# thread, one byte-budgeted trie, one wait-classification, one
# /profile payload that watchdog reports, flight bundles and the
# hot_frames verdict evidence all read). A sys._current_frames walk or
# a cProfile/profile/pstats import elsewhere in the package would be a
# second profiler the plane never sees. The list shrinks, it does not
# grow.
PROFILE_ALLOWED = {"dmlc_tpu/obs/profile.py"}
_PROFILER_MODULES = {"cProfile", "profile", "pstats"}


def profile_lint(paths: List[str],
                 trees: Optional[dict] = None) -> List[str]:
    """The profile gate: sys._current_frames / profiler-module imports
    confined to obs/profile.py (see above)."""
    if trees is None:
        trees = _parse_package_trees(paths)
    findings: List[str] = []
    for path in paths:
        if path not in trees:
            continue
        rel, tree = trees[path]
        if rel in PROFILE_ALLOWED:
            continue
        for node in ast.walk(tree):
            if ((isinstance(node, ast.Attribute)
                    and node.attr == "_current_frames"
                    and isinstance(node.value, ast.Name)
                    and node.value.id in ("sys", "_sys"))
                    or (isinstance(node, ast.ImportFrom)
                        and node.module == "sys" and node.level == 0
                        and any(a.name == "_current_frames"
                                for a in node.names))):
                findings.append(
                    f"{rel}:{node.lineno}: sys._current_frames outside "
                    "obs/profile.py — the process has ONE sampling "
                    "profiler (obs.profile.install()/sample_now()); "
                    "read its trie, don't walk frames ad hoc")
            mods = []
            if isinstance(node, ast.Import):
                mods = [a.name.split(".")[0] for a in node.names]
            elif isinstance(node, ast.ImportFrom) and node.module \
                    and node.level == 0:
                mods = [node.module.split(".")[0]]
            hit = sorted(set(mods) & _PROFILER_MODULES)
            if hit:
                findings.append(
                    f"{rel}:{node.lineno}: direct {'/'.join(hit)} "
                    "import outside obs/profile.py — profiling goes "
                    "through dmlc_tpu.obs.profile (StackProfiler / "
                    "hot_frames), one sampler per process")
    return findings


# Outbound HTTP is a SEAM: the objstore client modules
# (io/objstore/http_client.py — the real ranged-GET wire client —
# and io/objstore/peer.py — the gang /pages tier) plus obs/serve.py's
# scrape() are the ONLY package code that speaks http.client/
# urllib.request. Anywhere else, an ad-hoc urlopen would bypass the
# io.objstore.*/obs.scrape retry seams, the fault plans, and the
# byte counters that make remote traffic auditable. The list shrinks,
# it does not grow. (urllib.parse — pure string handling — is fine
# anywhere.)
HTTP_CLIENT_ALLOWED = {
    "dmlc_tpu/io/objstore/http_client.py",
    "dmlc_tpu/io/objstore/peer.py",
    "dmlc_tpu/obs/serve.py",
}
_HTTP_CLIENT_MODULES = {("http", "client"), ("urllib", "request")}


def http_client_lint(paths: List[str],
                     trees: Optional[dict] = None) -> List[str]:
    """The http-client gate: ``http.client``/``urllib.request``
    imports in dmlc_tpu/ confined to the objstore client modules and
    obs/serve.py (see above)."""
    if trees is None:
        trees = _parse_package_trees(paths)
    findings: List[str] = []
    for path in paths:
        if path not in trees:
            continue
        rel, tree = trees[path]
        if rel in HTTP_CLIENT_ALLOWED:
            continue
        for node in ast.walk(tree):
            hits = []
            if isinstance(node, ast.Import):
                for a in node.names:
                    parts = tuple(a.name.split(".")[:2])
                    if parts in _HTTP_CLIENT_MODULES:
                        hits.append(a.name)
            elif isinstance(node, ast.ImportFrom) and node.module \
                    and node.level == 0:
                parts = tuple(node.module.split(".")[:2])
                if parts in _HTTP_CLIENT_MODULES:
                    hits.append(node.module)
                elif node.module in ("http", "urllib"):
                    for a in node.names:
                        if (node.module, a.name) in \
                                _HTTP_CLIENT_MODULES:
                            hits.append(f"{node.module}.{a.name}")
            for hit in hits:
                findings.append(
                    f"{rel}:{node.lineno}: {hit} outside the objstore "
                    "client modules — outbound HTTP goes through "
                    "io/objstore/http_client.py, io/objstore/peer.py "
                    "or obs.serve.scrape() so retry seams, fault "
                    "plans and byte counters apply")
    return findings


# Raw sockets are a SEAM: dmlc_tpu/rendezvous/service.py is the ONE
# home for socket/socketserver construction (the TCP membership
# service, its line-protocol client transport, and the free-port
# probe parallel/launch.py re-exports), with obs/serve.py allowed for
# its HTTP plane (http.server builds on socketserver). Anywhere else,
# an ad-hoc socket would bypass the rendezvous wire protocol, the
# rendezvous.* retry seams, and the bounded-handler discipline. The
# list shrinks, it does not grow.
SOCKET_ALLOWED = {
    "dmlc_tpu/rendezvous/service.py",
    "dmlc_tpu/obs/serve.py",
}
_SOCKET_MODULES = {"socket", "socketserver"}


def socket_lint(paths: List[str],
                trees: Optional[dict] = None) -> List[str]:
    """The socket gate: ``socket``/``socketserver`` imports in
    dmlc_tpu/ confined to rendezvous/service.py and obs/serve.py
    (see above)."""
    if trees is None:
        trees = _parse_package_trees(paths)
    findings: List[str] = []
    for path in paths:
        if path not in trees:
            continue
        rel, tree = trees[path]
        if rel in SOCKET_ALLOWED:
            continue
        for node in ast.walk(tree):
            hits = []
            if isinstance(node, ast.Import):
                for a in node.names:
                    if a.name.split(".")[0] in _SOCKET_MODULES:
                        hits.append(a.name)
            elif isinstance(node, ast.ImportFrom) and node.module \
                    and node.level == 0:
                if node.module.split(".")[0] in _SOCKET_MODULES:
                    hits.append(node.module)
            for hit in hits:
                findings.append(
                    f"{rel}:{node.lineno}: {hit} import outside "
                    "rendezvous/service.py — raw TCP goes through "
                    "the rendezvous wire protocol (service.call / "
                    "probe_free_ports) so the bounded-handler "
                    "discipline and rendezvous.* retry seams apply")
    return findings


# the two pre-resilience "skip this file and move on" handlers (spill
# sweeps): genuinely skip-not-retry, pinned. New code classifies and
# retries through dmlc_tpu.resilience instead.
OSERROR_CONTINUE_ALLOWED = {
    "dmlc_tpu/data/row_iter.py",
    "dmlc_tpu/parallel/sharded.py",
}
RETRY_LOOP_ALLOWED: set = set()
_IO_EXC_NAMES = {"OSError", "IOError", "EnvironmentError",
                 "ConnectionError", "TimeoutError"}


def _handler_catches_io(handler: ast.ExceptHandler) -> bool:
    t = handler.type
    names = []
    if isinstance(t, ast.Name):
        names = [t.id]
    elif isinstance(t, ast.Tuple):
        names = [e.id for e in t.elts if isinstance(e, ast.Name)]
    return any(n in _IO_EXC_NAMES for n in names)


def _handler_swallows_io(handler: ast.ExceptHandler) -> bool:
    """Catches an I/O exception AND does not re-raise: a handler that
    converts to a typed error is classification, not a retry loop."""
    if not _handler_catches_io(handler):
        return False
    return not any(isinstance(n, ast.Raise) for n in ast.walk(handler))


def _walk_same_scope(stmts) -> List[ast.AST]:
    """Walk statements without descending into nested function/class
    definitions — a sleep inside a worker closure defined in a loop is
    not that loop retrying."""
    out: List[ast.AST] = []
    stack = list(stmts)
    while stack:
        n = stack.pop()
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                          ast.ClassDef)):
            continue
        out.append(n)
        stack.extend(ast.iter_child_nodes(n))
    return out


def _is_sleep_call(node: ast.AST) -> bool:
    if not isinstance(node, ast.Call):
        return False
    f = node.func
    return (isinstance(f, ast.Attribute) and f.attr == "sleep"
            and isinstance(f.value, ast.Name)
            and f.value.id in ("time", "_time")) or \
           (isinstance(f, ast.Name) and f.id == "sleep")


def resilience_lint(paths: List[str],
                    trees: Optional[dict] = None) -> List[str]:
    """The resilience gate: no hand-rolled sleep/retry loops and no
    naked ``except OSError: continue`` in dmlc_tpu/ outside
    dmlc_tpu/resilience/ (see module docstring)."""
    if trees is None:
        trees = _parse_package_trees(paths)
    findings: List[str] = []
    for path in paths:
        if path not in trees:
            continue
        rel, tree = trees[path]
        if rel.startswith("dmlc_tpu/resilience/"):
            continue
        for node in ast.walk(tree):
            if (isinstance(node, ast.ExceptHandler)
                    and _handler_catches_io(node)
                    and len(node.body) == 1
                    and isinstance(node.body[0], ast.Continue)
                    and rel not in OSERROR_CONTINUE_ALLOWED):
                findings.append(
                    f"{rel}:{node.lineno}: naked 'except OSError: "
                    "continue' — classify and retry through "
                    "dmlc_tpu.resilience (guarded()/RetryPolicy), or "
                    "log the skip")
            if (isinstance(node, (ast.While, ast.For))
                    and rel not in RETRY_LOOP_ALLOWED):
                body_nodes = _walk_same_scope(node.body)
                sleeps = any(_is_sleep_call(n) for n in body_nodes)
                catches = any(isinstance(n, ast.ExceptHandler)
                              and _handler_swallows_io(n)
                              for n in body_nodes)
                if sleeps and catches:
                    findings.append(
                        f"{rel}:{node.lineno}: hand-rolled sleep/"
                        "retry loop — use dmlc_tpu.resilience."
                        "RetryPolicy (guarded(site, fn)) so attempts/"
                        "backoff/classification are policy, not "
                        "control flow")
    return findings


# The steady path never iterates row payloads in Python (ISSUE 7: the
# engine's ABI-5 padded emission and the vectorized data.padding ops
# own the per-row work — PR 2 measured ~2× for eliminating one Python
# memcpy layer, and a `for row in block` loop is strictly worse).
# Inside dmlc_tpu/data/ and dmlc_tpu/pipeline/, a loop whose target is
# literally `row` or whose iterable is `range(<x>.size)`/
# `range(<x>.num_rows)` is per-row Python on the hot path. The golden
# Row protocol itself (RowBlock.__iter__/__getitem__ in
# data/rowblock.py — the DEBUGGING surface, not a steady-path stage)
# is pinned. The list shrinks, it does not grow.
ROW_LOOP_ALLOWED = {
    "dmlc_tpu/data/rowblock.py",
}
_ROW_LOOP_DIRS = ("dmlc_tpu/data/", "dmlc_tpu/pipeline/")


def _target_names(t: ast.AST) -> List[str]:
    return [n.id for n in ast.walk(t) if isinstance(n, ast.Name)]


def _iter_is_per_row(it: ast.AST) -> bool:
    """range(X.size) / range(X.num_rows): a per-row index loop."""
    if (isinstance(it, ast.Call) and isinstance(it.func, ast.Name)
            and it.func.id == "range"):
        for arg in it.args:
            for n in ast.walk(arg):
                if (isinstance(n, ast.Attribute)
                        and n.attr in ("size", "num_rows")):
                    return True
    return False


def row_loop_lint(paths: List[str],
                  trees: Optional[dict] = None) -> List[str]:
    """The steady-path gate: no per-row Python loops over block
    payloads in dmlc_tpu/data/ or dmlc_tpu/pipeline/ (see above)."""
    if trees is None:
        trees = _parse_package_trees(paths)
    findings: List[str] = []
    msg = ("per-row Python loop on the steady path — rows are engine "
           "work (dtp_parser_next_padded) or vectorized numpy "
           "(data.padding); stages operate on whole blocks")
    for path in paths:
        if path not in trees:
            continue
        rel, tree = trees[path]
        if (not rel.startswith(_ROW_LOOP_DIRS)
                or rel in ROW_LOOP_ALLOWED):
            continue
        for node in ast.walk(tree):
            if isinstance(node, ast.For):
                pairs = [(node.target, node.iter)]
            elif isinstance(node, (ast.ListComp, ast.SetComp,
                                   ast.DictComp, ast.GeneratorExp)):
                pairs = [(g.target, g.iter) for g in node.generators]
            else:
                continue
            for tgt, it in pairs:
                if "row" in _target_names(tgt) or _iter_is_per_row(it):
                    findings.append(f"{rel}:{node.lineno}: {msg}")
    return findings


# The analysis-verdict schema (dmlc_tpu/obs/analyze.py VERDICT_KEYS):
# the /analyze endpoint, the "analysis" blocks of archived BENCH JSON
# files, and scripts/obsctl.py all read THIS key set.
# The pin below is the one source of truth the gate checks everything
# against — change the schema by changing both, consciously.
VERDICT_KEYS = ("schema", "epoch", "verdict_id", "tenant", "bound",
                "band", "confidence", "evidence", "hot_frames",
                "stage_waits")
_ANALYZE_REL = "dmlc_tpu/obs/analyze.py"


def _const_str_keys(node: ast.Dict) -> Optional[List[str]]:
    """The dict's keys when ALL are string constants, else None (a
    dynamic key means the dict is not a literal verdict shape)."""
    keys = []
    for k in node.keys:
        if not (isinstance(k, ast.Constant) and isinstance(k.value, str)):
            return None
        keys.append(k.value)
    return keys


def verdict_lint(paths: List[str],
                 trees: Optional[dict] = None) -> List[str]:
    """The verdict-schema gate: every literal dict that claims to BE a
    verdict (carries both a "bound" and an "evidence" string key) must
    carry exactly the pinned VERDICT_KEYS, and obs/analyze.py's
    VERDICT_KEYS tuple must equal the pin. Scanned over dmlc_tpu/ and
    scripts/ — the CLI consumes the same schema."""
    if trees is None:
        trees = _parse_package_trees(paths)
    scan: List[tuple] = [trees[p] for p in paths if p in trees]
    scripts_dir = os.path.join(REPO, "scripts")
    for path in paths:
        rel = os.path.relpath(path, REPO).replace(os.sep, "/")
        if not path.startswith(scripts_dir + os.sep):
            continue
        try:
            with open(path, "r", encoding="utf-8") as f:
                scan.append((rel, ast.parse(f.read(), filename=rel)))
        except (OSError, SyntaxError, UnicodeDecodeError):
            pass
    findings: List[str] = []
    pin_seen = False
    for rel, tree in scan:
        for node in ast.walk(tree):
            if (rel == _ANALYZE_REL and isinstance(node, ast.Assign)
                    and any(isinstance(t, ast.Name)
                            and t.id == "VERDICT_KEYS"
                            for t in node.targets)):
                pin_seen = True
                v = node.value
                vals = (tuple(e.value for e in v.elts
                              if isinstance(e, ast.Constant))
                        if isinstance(v, (ast.Tuple, ast.List))
                        else None)
                if vals != VERDICT_KEYS:
                    findings.append(
                        f"{rel}:{node.lineno}: VERDICT_KEYS {vals!r} "
                        f"drifted from the lint pin {VERDICT_KEYS!r} "
                        "— the /analyze endpoint, bench JSON and "
                        "obsctl share this schema; change both "
                        "consciously")
            if isinstance(node, ast.Dict):
                keys = _const_str_keys(node)
                if (keys is not None and "bound" in keys
                        and "evidence" in keys
                        and "outcome" not in keys
                        and sorted(keys) != sorted(VERDICT_KEYS)):
                    # ("outcome" marks a control-plane DECISION record
                    # — it cites a verdict by id, it is not one; its
                    # shape is pinned by obs/control.py RECORD_KEYS)
                    findings.append(
                        f"{rel}:{node.lineno}: verdict-shaped dict "
                        f"with keys {sorted(keys)} != the pinned "
                        f"schema {sorted(VERDICT_KEYS)} — build "
                        "verdicts with dmlc_tpu.obs.analyze."
                        "attribute(), never by hand")
    if any(rel == _ANALYZE_REL for rel, _ in scan) and not pin_seen:
        findings.append(f"{_ANALYZE_REL}:0: VERDICT_KEYS tuple "
                        "missing (the verdict-schema gate pins it)")
    return findings


# Knob mutation is a PLANE, not a call-site choice: every steady-state
# tunable (queue capacities via set_capacity, the shard serve depth,
# the in-flight device window, the objstore coalesce/parallel/codec
# options) is moved ONLY by the exploration rails — the depth
# hill-climber (pipeline/autotune.py) and the verdict-driven
# controller (obs/control.py) — so every move lands in the decision
# ledger with the evidence that caused it. A direct set_capacity or
# configure(coalesce=...) elsewhere in the package would be a
# hand-tuned constant the /control surface never saw. Pinned
# exceptions: the modules that DEFINE the knobs (threaded_iter's
# set_capacity itself, graph.py's knob get/set closures and stage
# construction, sharded.py's initial depth) and pagestore's budget
# plumbing. The list shrinks, it does not grow.
KNOB_MUTATION_ALLOWED = {
    "dmlc_tpu/pipeline/autotune.py",   # the hill-climber (rails)
    "dmlc_tpu/obs/control.py",         # the verdict-driven controller
    "dmlc_tpu/pipeline/graph.py",      # knob closures defined here
    "dmlc_tpu/data/threaded_iter.py",  # set_capacity definition
    "dmlc_tpu/parallel/sharded.py",    # initial prefetch_depth
}
# configure(coalesce=/parallel=/codec_level=) additionally allowed
# where the option plane is DEFINED
KNOB_CONFIGURE_ALLOWED = KNOB_MUTATION_ALLOWED | {
    "dmlc_tpu/io/objstore/fs.py",      # configure() itself
}
_KNOB_ATTRS = {"prefetch_depth", "window"}
_KNOB_CONFIGURE_KWARGS = {"coalesce", "parallel", "codec_level"}


def knob_lint(paths: List[str],
              trees: Optional[dict] = None) -> List[str]:
    """The knob gate: steady-state knob mutation (``.set_capacity()``
    calls, ``.prefetch_depth``/``.window`` attribute assignment,
    ``configure()`` with coalesce/parallel/codec_level) confined to
    the exploration rails (see KNOB_MUTATION_ALLOWED)."""
    if trees is None:
        trees = _parse_package_trees(paths)
    findings: List[str] = []
    for path in paths:
        if path not in trees:
            continue
        rel, tree = trees[path]
        for node in ast.walk(tree):
            if (rel not in KNOB_MUTATION_ALLOWED
                    and isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "set_capacity"):
                findings.append(
                    f"{rel}:{node.lineno}: direct set_capacity() — "
                    "queue depths are knobs; move them through the "
                    "exploration rails (pipeline/autotune.py Autotuner "
                    "or obs/control.py Controller) so the decision "
                    "lands in the ledger")
            if (rel not in KNOB_MUTATION_ALLOWED
                    and isinstance(node, (ast.Assign, ast.AugAssign,
                                          ast.AnnAssign))):
                # every assignment form counts: plain, augmented
                # (`w.window += 8`), annotated, and tuple-unpack
                # targets. Only the ASSIGNED attribute itself matters
                # — a knob attribute READ inside a target (a subscript
                # index, an attribute-chain prefix) is not a mutation.
                targets = (node.targets if isinstance(node, ast.Assign)
                           else [node.target])
                direct = []
                stack = list(targets)
                while stack:
                    t = stack.pop()
                    if isinstance(t, (ast.Tuple, ast.List)):
                        stack.extend(t.elts)
                    elif isinstance(t, ast.Starred):
                        stack.append(t.value)
                    elif isinstance(t, ast.Attribute):
                        direct.append(t)
                attrs = sorted({t.attr for t in direct
                                if t.attr in _KNOB_ATTRS})
                for attr in attrs:
                    findings.append(
                        f"{rel}:{node.lineno}: direct .{attr} "
                        "assignment — a steady-state knob moves "
                        "through the exploration rails "
                        "(autotune/control), never a hand-set "
                        "constant")
            if (rel not in KNOB_CONFIGURE_ALLOWED
                    and isinstance(node, ast.Call)
                    and ((isinstance(node.func, ast.Attribute)
                          and node.func.attr == "configure")
                         or (isinstance(node.func, ast.Name)
                             and node.func.id == "configure"))):
                hit = sorted(kw.arg for kw in node.keywords
                             if kw.arg in _KNOB_CONFIGURE_KWARGS)
                if hit:
                    findings.append(
                        f"{rel}:{node.lineno}: configure("
                        f"{'/'.join(hit)}=...) outside the control "
                        "plane — the wire knobs (coalesce, parallel, "
                        "codec level) are moved by obs/control.py "
                        "against the /analyze verdict; see "
                        "docs/remote_io.md")
    return findings


def run_ruff(root: str = REPO) -> Optional[List[str]]:
    """ruff findings, or None when ruff is not installed."""
    cmd = None
    try:
        import ruff  # noqa: F401 — presence probe only
        cmd = [sys.executable, "-m", "ruff"]
    except ImportError:
        from shutil import which
        if which("ruff"):
            cmd = ["ruff"]
    if cmd is None:
        return None
    proc = subprocess.run(
        cmd + ["check", "--quiet", root],
        capture_output=True, text=True, timeout=300)
    if proc.returncode == 0:
        return []
    out = (proc.stdout + proc.stderr).strip()
    return [line for line in out.splitlines() if line.strip()]


def run_clang_format(root: str = NATIVE_SRC) -> Optional[List[str]]:
    """clang-format dry-run findings, or None when unavailable."""
    from shutil import which
    if which("clang-format") is None:
        return None
    files = native_files(root)
    if not files:
        return []
    proc = subprocess.run(
        ["clang-format", "--dry-run", "-Werror"] + files,
        capture_output=True, text=True, timeout=300)
    if proc.returncode == 0:
        return []
    return [line for line in proc.stderr.splitlines() if line.strip()]


# Thread construction in the pipeline layer is a BUDGET, not a
# call-site choice: the multi-tenant scheduler (pipeline/scheduler.py)
# owns the process's thread/queue budgets and time-slices them across
# tenants — a stage runner spawning its own threading.Thread or pool
# would be capacity the scheduler can neither see, bill, nor
# backpressure. Pipeline stages get concurrency by lowering onto the
# ALREADY-BUDGETED machinery (data/threaded_iter.ThreadedIter — the
# one audited producer-thread seam, whose capacities the scheduler
# rebalances — and the native engine's own pools). The list shrinks,
# it does not grow.
THREAD_ALLOWED = {
    "dmlc_tpu/pipeline/scheduler.py",  # the budget owner itself
}
_THREAD_DIR = "dmlc_tpu/pipeline/"
_POOL_NAMES = {"ThreadPoolExecutor", "ProcessPoolExecutor", "Pool"}


def thread_lint(paths: List[str],
                trees: Optional[dict] = None) -> List[str]:
    """The thread gate: threading.Thread / executor-pool construction
    in dmlc_tpu/pipeline/ confined to the scheduler module (see
    above)."""
    if trees is None:
        trees = _parse_package_trees(paths)
    findings: List[str] = []
    for path in paths:
        if path not in trees:
            continue
        rel, tree = trees[path]
        if not rel.startswith(_THREAD_DIR) or rel in THREAD_ALLOWED:
            continue
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            name = None
            if (isinstance(f, ast.Attribute)
                    and isinstance(f.value, ast.Name)
                    and f.value.id in ("threading", "_threading")
                    and f.attr == "Thread"):
                name = "threading.Thread"
            elif isinstance(f, ast.Name) and f.id == "Thread":
                name = "Thread"
            elif (isinstance(f, ast.Attribute)
                  and f.attr in _POOL_NAMES) \
                    or (isinstance(f, ast.Name)
                        and f.id in _POOL_NAMES):
                name = (f.attr if isinstance(f, ast.Attribute)
                        else f.id)
            if name:
                findings.append(
                    f"{rel}:{node.lineno}: {name} construction in the "
                    "pipeline layer outside scheduler.py — thread "
                    "capacity is a scheduler-owned budget; lower onto "
                    "ThreadedIter (data/threaded_iter.py) or the "
                    "native engine's pools instead")
    return findings


# Trace-context wire literals are a SEAM: dmlc_tpu/obs/rpc.py is the
# ONE home for the "X-Dmlc-Trace"/"X-Dmlc-Handle-Us" header names and
# the serialized context format. Every other module injects/extracts
# through rpc.inject()/rpc.extract() and the TRACE_HEADER/HANDLE_HEADER
# constants — a hand-spelled header string would silently fork the wire
# format the flow-linked gang timelines depend on. The list is one
# entry and stays one entry.
TRACE_HEADER_ALLOWED = {
    "dmlc_tpu/obs/rpc.py",
}
_TRACE_HEADER_LITERALS = {"X-Dmlc-Trace", "X-Dmlc-Handle-Us"}


def trace_header_lint(paths: List[str],
                      trees: Optional[dict] = None) -> List[str]:
    """The trace-header gate: the ``X-Dmlc-Trace``/``X-Dmlc-Handle-Us``
    wire literals in dmlc_tpu/ confined to obs/rpc.py (see above)."""
    if trees is None:
        trees = _parse_package_trees(paths)
    findings: List[str] = []
    for path in paths:
        if path not in trees:
            continue
        rel, tree = trees[path]
        if rel in TRACE_HEADER_ALLOWED:
            continue
        for node in ast.walk(tree):
            if isinstance(node, ast.Constant) \
                    and isinstance(node.value, str) \
                    and node.value in _TRACE_HEADER_LITERALS:
                findings.append(
                    f"{rel}:{node.lineno}: {node.value!r} literal "
                    "outside obs/rpc.py — the trace-context wire "
                    "format is owned by dmlc_tpu.obs.rpc; use "
                    "rpc.TRACE_HEADER/rpc.HANDLE_HEADER and the "
                    "inject()/extract() helpers")
    return findings


# The slo.* metric family and the burn-rate alert thresholds belong to
# dmlc_tpu/obs/slo.py — ONE home for the alert math. A hand-spelled
# "slo.xxx" gauge elsewhere would fork the family the /slo surfaces
# render, and a re-spelled 14.4/6.0 would fork the SRE-workbook
# thresholds every consumer imports as FAST_BURN_RATE/SLOW_BURN_RATE.
SLO_ALLOWED = {
    "dmlc_tpu/obs/slo.py",
}
_BURN_RATE_LITERALS = {14.4, 6.0}
# non-alert uses of the bare numbers, pinned: supervise.py's 6.0 is a
# gang-teardown drain margin (deadline - 6.0), not burn-rate math
BURN_RATE_EXEMPT = {
    "dmlc_tpu/resilience/supervise.py",
}


def _slo_instrument_name(call: ast.Call) -> bool:
    """True when an instrument call's literal (or f-string) name sits
    in the slo.* family."""
    if not call.args:
        return False
    a = call.args[0]
    if isinstance(a, ast.Constant) and isinstance(a.value, str):
        return a.value.startswith("slo.")
    if isinstance(a, ast.JoinedStr) and a.values:
        first = a.values[0]
        return (isinstance(first, ast.Constant)
                and isinstance(first.value, str)
                and first.value.startswith("slo."))
    return False


def slo_lint(paths: List[str],
             trees: Optional[dict] = None) -> List[str]:
    """The SLO gate: ``slo.*`` instrument names and the burn-rate
    threshold floats confined to obs/slo.py (see above)."""
    if trees is None:
        trees = _parse_package_trees(paths)
    findings: List[str] = []
    for path in paths:
        if path not in trees:
            continue
        rel, tree = trees[path]
        if rel in SLO_ALLOWED:
            continue
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in _INSTRUMENT_METHODS
                    and _slo_instrument_name(node)):
                findings.append(
                    f"{rel}:{node.lineno}: slo.* instrument outside "
                    "obs/slo.py — the slo.* metric family is owned by "
                    "dmlc_tpu.obs.slo (the engine exports the "
                    "per-objective gauges itself)")
            elif (isinstance(node, ast.Constant)
                    and isinstance(node.value, float)
                    and node.value in _BURN_RATE_LITERALS
                    and rel not in BURN_RATE_EXEMPT):
                findings.append(
                    f"{rel}:{node.lineno}: burn-rate threshold "
                    f"{node.value!r} outside obs/slo.py — import "
                    "FAST_BURN_RATE/SLOW_BURN_RATE from "
                    "dmlc_tpu.obs.slo (one home for alert math)")
    return findings


# Seeded permutations are a SEAM: dmlc_tpu/shuffle/ (epoch_rng /
# GlobalShuffle) is the ONE home for RNG construction in the data
# path — ad-hoc `random` / `numpy.random` use inside dmlc_tpu/io/ or
# dmlc_tpu/data/ would mint a shuffle order the determinism contract
# (same seed ⇒ same global order at any world size, restart-stable
# resume) never sees. The list shrinks, it does not grow.
RANDOM_ALLOWED: set = set()
_RANDOM_DIRS = ("dmlc_tpu/io/", "dmlc_tpu/data/")


def random_lint(paths: List[str],
                trees: Optional[dict] = None) -> List[str]:
    """The random gate: ``random``/``numpy.random`` construction in
    dmlc_tpu/io/ + dmlc_tpu/data/ confined to dmlc_tpu/shuffle/
    (see above)."""
    if trees is None:
        trees = _parse_package_trees(paths)
    findings: List[str] = []
    for path in paths:
        if path not in trees:
            continue
        rel, tree = trees[path]
        if not rel.startswith(_RANDOM_DIRS) or rel in RANDOM_ALLOWED:
            continue
        for node in ast.walk(tree):
            hits = []
            if isinstance(node, ast.Import):
                for a in node.names:
                    root = a.name.split(".")[0]
                    if root == "random" or a.name.startswith(
                            "numpy.random"):
                        hits.append(a.name)
            elif isinstance(node, ast.ImportFrom) and node.level == 0:
                mod = node.module or ""
                if mod == "random" or mod.startswith("numpy.random"):
                    hits.append(mod)
                elif mod == "numpy":
                    hits.extend(f"numpy.{a.name}" for a in node.names
                                if a.name == "random")
            elif (isinstance(node, ast.Attribute)
                    and node.attr == "random"
                    and isinstance(node.value, ast.Name)
                    and node.value.id in ("np", "numpy")):
                hits.append(f"{node.value.id}.random")
            for hit in hits:
                findings.append(
                    f"{rel}:{node.lineno}: {hit} in the data path — "
                    "seeded permutations have one home: draw epoch "
                    "randomness from dmlc_tpu.shuffle.permutation."
                    "epoch_rng (or lower onto GlobalShuffle) so the "
                    "determinism contract holds")
    return findings


def main() -> int:
    paths = python_files()
    findings = builtin_lint(paths)
    trees = _parse_package_trees(paths)  # one parse, both AST gates
    findings += obs_lint(paths, trees)
    findings += metric_lint(paths, trees)
    findings += resilience_lint(paths, trees)
    findings += io_seam_lint(paths, trees)
    findings += row_loop_lint(paths, trees)
    findings += verdict_lint(paths, trees)
    findings += knob_lint(paths, trees)
    findings += codec_lint(paths, trees)
    findings += arrow_lint(paths, trees)
    findings += profile_lint(paths, trees)
    findings += http_client_lint(paths, trees)
    findings += socket_lint(paths, trees)
    findings += thread_lint(paths, trees)
    findings += trace_header_lint(paths, trees)
    findings += slo_lint(paths, trees)
    findings += random_lint(paths, trees)
    ruff = run_ruff()
    if ruff is None:
        print("lint: ruff not installed — built-in checks only",
              file=sys.stderr)
    else:
        findings += ruff
    cf = run_clang_format()
    if cf is None:
        print("lint: clang-format not installed — native/src unchecked",
              file=sys.stderr)
    else:
        findings += cf
    for f in findings:
        print(f, file=sys.stderr)
    print(f"lint: {len(findings)} finding(s) over "
          f"{len(paths)} python files", file=sys.stderr)
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
