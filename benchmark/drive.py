"""The one general driver. A cell is a configuration file, a traffic
file and a limits file, all found by name from ``BENCHMARK.json``; the
traffic names its consumer (``benchmark/consumers/<kind>.py``) and the
pipeline steps it adds, the configuration its text format (the
reference parse in ``benchmark/ref/<format>.py``); the per-layer metrics
are readers under ``benchmark/metrics/``, found by their names.

A run: set-up (JAX and the chips, the corpus, the program's pipeline
built with its defaults, the consumer's first steps through the
window's own call and feed), then a window of ``seconds`` that pulls
batches into HBM and feeds the consumer, then the check: batches drawn
from the seed are read back from HBM and compared row by row with the
reference's parse of the same text, every finished epoch must have
delivered every row once, and the consumer's first steps are compared
with the reference's replay. The reference runs after the window, with
the program's device state freed.

A traffic that says its rows arrive out of source order (``"rows":
"out_of_order"``, with steps before the parse such as a shuffle) has
each checked row found in the corpus by its content (``locate``), and
two more checks: no content delivered in an epoch more often than the
rows of the part that hold it, over every row the window delivers (a
key of each row's content is taken on the device as it is fed), and no
checked window batch exactly where source order would put it.

A traffic that states the parse engine its deployment needs
(``"engine": "native"`` or ``"python"``) is held to it by one more
check, ``engine_served``. The harness does not ask for that engine: it
judges which engine served the epochs the run finished, so a program
that falls back to another engine comes out not correct.
"""

from __future__ import annotations

import importlib.util
import json
import os
import shutil
import time
from typing import Any, Dict, List, Optional

import numpy as np

from benchmark import locate, ref
from benchmark.gen import CACHE, Stream, ensure_corpus, generator

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
# window batches drawn from the seed for the row-by-row comparison
SAMPLED_BATCHES = 4
# a step argument that takes the run's --seed
RUN_SEED = "<run seed>"


class NoDevice(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_cell(name: str, root: str = ROOT) -> Dict[str, Any]:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    centry = next(c for c in bench["configs"] if c["name"] == cell["config"])

    def data(*path):
        with open(os.path.join(root, *path)) as f:
            return json.load(f)

    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name])
                 and m["moves"] in reported]
    return {"cell": cell, "cfg": data(centry["file"]),
            "traffic": data("benchmark", "traffic", cell["traffic"] + ".json"),
            "limits": data("benchmark", "limits", name + ".json"),
            "end_to_end": e2e, "per_layer": per_layer}


def chips(n: int, require_tpu: bool = True):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoDevice(f"JAX found {devs[0].platform} devices, no TPU")
    if len(devs) < n:
        raise NoDevice(f"the cell needs {n} chips, JAX found {len(devs)}")
    return devs[:n]


def persists(traffic: dict) -> bool:
    """Whether a step of the traffic keeps what it makes on disk across
    runs (it names a ``path``). Such a traffic reads the corpus in its
    canonical order: what it keeps is made once per checkout, not per
    seed."""
    return any("path" in kw for _, kw in traffic.get("steps", []))


def out_of_order(traffic: dict) -> bool:
    """Whether the traffic's rows arrive out of source order."""
    return traffic.get("rows") == "out_of_order"


def build_pipeline(cfg: dict, traffic: dict, uri: str, devs, cache: str,
                   seed: int):
    """The program's pipeline for this cell, through its user entry
    points, setting only the sizes the configuration fixes: the steps
    the traffic names before the parse (``before_parse``; an argument
    given as ``"<run seed>"`` takes ``seed``), the parse (the native
    engine where nothing comes before it, else the program's own
    choice), the steps it names after (``steps``; ``[method,
    arguments]`` each; a ``path`` is a file name under the cache, per
    configuration and generator version), then padded batches to the
    chip, or the shard onto a mesh of the cell's chips."""
    from dmlc_tpu.pipeline import Pipeline
    rows = cfg["batch_rows"]
    nnz = rows * cfg["nnz_per_row"]
    pipe = Pipeline.from_uri(uri)
    before = traffic.get("before_parse", [])
    for step, kw in before:
        pipe = getattr(pipe, step)(**{k: seed if v == RUN_SEED else v
                                      for k, v in kw.items()})
    pipe = pipe.parse(**cfg["parse"]) if before else pipe.parse(engine="native", **cfg["parse"])
    for step, kw in traffic.get("steps", []):
        if "path" in kw:
            kw = dict(kw, path=os.path.join(
                cache, "pages", f"{cfg['name']}-v{generator(cfg).VERSION}.{kw['path']}"))
        pipe = getattr(pipe, step)(**kw)
    if len(devs) > 1:
        from jax.sharding import Mesh
        mesh = Mesh(np.array(devs), ("data",))
        return pipe.shard(mesh, row_bucket=rows, nnz_bucket=nnz,
                          **traffic.get("shard", {})).build(), mesh
    return pipe.batch(rows, pad=True, nnz_bucket=nnz).to_device(devs[0]).build(), None


def consumer(kind: str, cfg: dict, devs, mesh):
    """The consumer named ``kind``, from ``benchmark/consumers/<kind>.py``."""
    return importlib.import_module(f"benchmark.consumers.{kind}").Consumer(cfg, devs, mesh)


def _readers(per_layer: List[dict]):
    out = []
    for m in per_layer:
        path = os.path.join(BENCH, "metrics", m["name"] + ".py")
        spec = importlib.util.spec_from_file_location(
            "benchmark.metrics." + m["name"].replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        out.append((m, mod.read))
    return out


def served_by(stats: Optional[dict]) -> Dict[str, Optional[str]]:
    """The parse engine and the assembly path that served the newest
    finished epoch, as its stats snapshot names them: the native engine
    leaves its counters under a stage's ``engine``, the Python golden
    none; a page replay parses nothing (``engine`` None)."""
    stages = (stats or {}).get("stages", [])
    extra = [st.get("extra") or {} for st in stages]
    kinds = [st["kind"] for st in stages]
    engine = ("native" if any("engine" in x for x in extra)
              else "python" if "parse" in kinds else None)
    path = next((x["assembly_path"] for x in extra if "assembly_path" in x),
                "shard" if "shard" in kinds else None)
    out = {"engine": engine, "assembly_path": path}
    out.update({"replay_tier": x["replay_tier"] for x in extra if "replay_tier" in x})
    return out


def engine_served(traffic: dict, snapshots: List[Optional[dict]]) -> Optional[int]:
    """0 where every epoch the run finished (``snapshots``, one stats
    snapshot each) was parsed by the engine the traffic states, else 1;
    a run that finished no epoch has not shown its engine, and reads 1.
    None where the traffic states no engine."""
    want = traffic.get("engine")
    if want is None:
        return None
    return int(not snapshots or any(served_by(s)["engine"] != want for s in snapshots))


def locate_rows(loc: "locate.Locator", stream: Stream, first: np.ndarray,
                host_samples: Dict[int, Any], epochs: np.ndarray, lo: np.ndarray):
    """The source row of each row of each checked batch, by content,
    within its chip's byte-range part. Rows with one content are a
    multiset: within an epoch, each delivery takes the next such source
    row. A row whose content no source row of the part holds takes the
    row that source order puts in its place (``lo``, the first row of
    each batch on each chip in source order), which the exact
    comparison then counts as mismatched."""
    used: Dict[tuple, int] = {}
    found: Dict[tuple, np.ndarray] = {}
    for i in sorted(host_samples):
        for d, shard in enumerate(ref.shards(host_samples[i])):
            keys = locate.row_keys(ref.device_rows(shard))
            out = lo[i, d] + np.arange(len(keys))
            for j, (k, c) in enumerate(zip(keys.tolist(), loc.candidates(
                    stream, keys, int(first[d]), int(first[d + 1])))):
                if len(c):
                    u = used.get((epochs[i], d, k), 0)
                    used[epochs[i], d, k] = u + 1
                    out[j] = c[u % len(c)]
            found[i, d] = out
    return found


def epoch_bytes(num_rows: np.ndarray, epochs: np.ndarray, epoch: int,
                stream: Stream, first: np.ndarray) -> List[float]:
    """Text bytes of each epoch's delivered rows where they arrive out
    of source order: a finished epoch (before ``epoch``) counts its
    parts' exact bytes, the unfinished one its rows on each chip times
    that chip's part's mean bytes a row."""
    part_bytes = np.diff(stream.start[first])
    out: List[float] = []
    for e in np.unique(epochs):
        if e < epoch:
            out.append(int(part_bytes.sum()))
        else:
            rows = num_rows[epochs == e].sum(axis=0)
            out.append(float((rows * part_bytes / np.diff(first)).sum()))
    return out


def run(name: str, seed: int, seconds: float, trace: bool, t0: float,
        root: str = ROOT, cache: str = CACHE, require_tpu: bool = True) -> Dict[str, Any]:
    """One run of cell ``name``. Returns the result line's object; the
    numbers compared come last, under ``checks``."""
    spec = load_cell(name, root)
    cell, cfg, traffic = spec["cell"], spec["cfg"], spec["traffic"]
    import dmlc_tpu  # noqa: F401 — the program under test: without it, stop here
    import jax
    devs = chips(cell["chips"], require_tpu)
    jax.config.update("jax_compilation_cache_dir", os.path.join(cache, "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles: List[float] = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, secs, **_: compiles.append(time.perf_counter())
        if ev == "/jax/core/compile/backend_compile_duration" else None)

    corpus, corpus_info = ensure_corpus(cfg, cache)
    stream = corpus.stream(None if persists(traffic) else seed)
    first = stream.part_first_rows(len(devs))
    built, mesh = build_pipeline(cfg, traffic, stream.uri, devs, cache, seed)
    feed = consumer(traffic["consumer"], cfg, devs, mesh)
    readers = _readers(spec["per_layer"]) if trace else []
    shuffled = out_of_order(traffic)
    # rows out of source order: each delivered row's content key (its
    # first lane), kept on the device until the window has closed
    keyfn = jax.jit(locate.device_keys) if shuffled else None
    content_keys: List[Any] = []

    epoch = 0
    delivered: List[tuple] = []   # (epoch, num_rows on the device)
    samples: Dict[int, Any] = {}  # delivered index → batch, checked after
    resident: List[Any] = []
    it = iter(built)

    def pull():
        nonlocal it, epoch
        with jax.profiler.TraceAnnotation("bench.next_batch"):
            b = next(it, None)
            if b is None:
                epoch_end()
                it = iter(built)
                b = next(it)
        with jax.profiler.TraceAnnotation("bench.block_until_ready"):
            jax.block_until_ready(b)
        return b

    snapshots: List[dict] = []
    last: List[Any] = [None]
    pinned = set()

    def epoch_end():
        nonlocal epoch
        snapshots.append(built.stats())
        if epoch == 0:  # the first epoch's short last batch is checked too
            pinned.add(len(delivered) - 1)
            samples[len(delivered) - 1] = last[0]
        resident.clear()
        feed.reset()
        epoch += 1

    def take(b):
        delivered.append((epoch, b["num_rows"]))
        last[0] = b
        if shuffled:
            content_keys.append(keyfn(b))
        if feed.KEEP_RESIDENT:
            resident.append(b)
        with jax.profiler.TraceAnnotation("bench.train_step"):
            feed(b)

    # set-up: the consumer's first steps through the window's own pull
    # and feed
    for _ in range(feed.FIRST_STEPS):
        b = pull()
        samples[len(delivered)] = b
        take(b)
    jax.block_until_ready(feed.state)
    n_setup = len(delivered)

    log_dir = os.path.join(cache, "trace")
    if trace:
        shutil.rmtree(log_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)

    rng = np.random.default_rng([seed % (1 << 64), 1])
    want = SAMPLED_BATCHES
    reservoir: List[int] = []
    waits: List[float] = []
    w_start = time.perf_counter()
    deadline = w_start + seconds
    window = jax.profiler.TraceAnnotation("bench.window")
    window.__enter__()
    while True:
        t = time.perf_counter()
        b = pull()
        waits.append(time.perf_counter() - t)
        j = len(delivered) - n_setup
        if j < want:
            reservoir.append(len(delivered))
            samples[len(delivered)] = b
        else:
            r = int(rng.integers(0, j + 1))
            if r < want:
                if reservoir[r] not in pinned:
                    samples.pop(reservoir[r])
                reservoir[r] = len(delivered)
                samples[len(delivered)] = b
        take(b)
        # a traced run closes only once a whole epoch has passed, so
        # that each per-layer reader finds a finished epoch to read
        if time.perf_counter() >= deadline and (not trace or snapshots):
            break
    jax.block_until_ready(feed.state)
    w_end = time.perf_counter()
    window.__exit__(None, None, None)
    window_s = w_end - w_start
    compiles_in_window = sum(w_start <= c <= w_end for c in compiles)
    if trace:
        jax.profiler.stop_trace()

    # -- the window has closed: memory, read-back, then free the program
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)
    host_samples = {i: jax.device_get(b) for i, b in samples.items()}
    num_rows = np.asarray(jax.device_get([n for _, n in delivered]),
                          np.int64).reshape(len(delivered), -1)
    epochs = np.asarray([e for e, _ in delivered])
    if shuffled:
        lanes = np.asarray(jax.device_get(content_keys))
        lanes = lanes.reshape(len(delivered), -1, lanes.shape[-1])
        content_keys.clear()
    served = served_by(built.stats())
    feed.close()
    samples.clear()
    resident.clear()
    last[0] = b = None
    it = None
    built.close()

    # -- rows of every delivered batch where source order puts them
    starts = np.zeros_like(num_rows)
    for e in np.unique(epochs):
        sel = np.flatnonzero(epochs == e)
        starts[sel] = np.cumsum(num_rows[sel], axis=0) - num_rows[sel]
    lo = first[:-1][None, :] + starts
    part_rows = np.diff(first)
    done = [e for e in np.unique(epochs) if e < epoch]
    checks: Dict[str, Dict[str, Any]] = {}
    engine = engine_served(traffic, snapshots)
    if engine is not None:
        checks["engine_served"] = {"value": engine}
    if done:
        gap = max(int(np.abs(num_rows[epochs == e].sum(axis=0) - part_rows).sum())
                  for e in done)
        checks["epoch_rows_gap"] = {"value": gap}

    # -- the source row of each checked row (in source order its place;
    # out of it, the row the locator finds by content), and the text
    # bytes of the window's batches
    if shuffled:
        loc, loc_info = locate.ensure_locator(cfg, corpus)
        found = locate_rows(loc, stream, first, host_samples, epochs, lo)
        repeated = loc.excess(stream, first, lanes, num_rows, epochs)

        def src(i: int, d: int) -> np.ndarray:
            return found[i, d]

        # less the set-up's rows, found by content
        row_bytes = np.diff(stream.start)
        setup_bytes = sum(int(row_bytes[src(i, d)].sum())
                          for i in range(n_setup) for d in range(len(devs)))
        window_bytes = sum(epoch_bytes(num_rows, epochs, epoch, stream, first)) - setup_bytes
    else:
        def src(i: int, d: int) -> np.ndarray:
            return lo[i, d] + np.arange(num_rows[i, d])

        nbytes = (stream.start[lo + num_rows] - stream.start[lo]).sum(axis=1)
        window_bytes = int(nbytes[n_setup:].sum())

    # -- the reference: sampled batches row by row, then the consumer's
    # first steps
    parse = ref.parser(cfg["parse"]["format"])

    def ref_rows(i: int, d: int):
        return parse(stream.lines_at(src(i, d)), **cfg["parse"])

    bad = 0
    for i, hb in host_samples.items():
        for d, shard in enumerate(ref.shards(hb)):
            bad += ref.rows_mismatched(ref.device_rows(shard), ref_rows(i, d))
    checks["rows_mismatched"] = {"value": bad}
    if shuffled:
        checks["rows_repeated"] = {"value": repeated}
        checks["rows_in_source_order"] = {"value": sum(
            all(np.array_equal(src(i, d), lo[i, d] + np.arange(num_rows[i, d]))
                for d in range(len(devs)))
            for i in host_samples if i >= n_setup)}
    checks.update({k: {"value": v} for k, v in feed.check(
        lambda i: ref.concat([ref_rows(i, d) for d in range(len(devs))])).items()})
    for k, c in checks.items():
        c["limit"] = spec["limits"][k]["limit"]
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    out: Dict[str, Any] = {"correct": correct,
                           "attempted": len(delivered) - n_setup,
                           "failed": 0}
    if trace:
        from benchmark import trace_reduce
        red = trace_reduce.reduce_dir(log_dir)
        shutil.rmtree(log_dir, ignore_errors=True)
        ctx = {"stats": snapshots, "trace": red, "window_s": window_s,
               "epoch_text_bytes": stream.total_bytes,
               "consumer_module": feed.module}
        metrics = {}
        for m, read in readers:
            v = read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        out.update(metrics=metrics, device=device,
                   breakdown={"device_ops": red["device_ops"],
                              "idle_gaps": red["idle_gaps"]})
    else:
        w = np.asarray(waits)
        values = {
            "ingest_gbps": window_bytes / window_s / len(devs) / 1e9,
            "batch_wait_p95_ms": float(np.percentile(w, 95)) * 1e3,
            "setup_s": w_start - t0,
        }
        out.update(metrics={m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                            for m in spec["end_to_end"]},
                   device=device)
    out["info"] = {"window_s": window_s, "epochs_done": len(done),
                   "batches": len(delivered) - n_setup,
                   "compiles_in_window": compiles_in_window,
                   "corpus_generated": corpus_info["generated"],
                   "corpus_s": corpus_info["corpus_s"],
                   "corpus_bytes": corpus_info["bytes"], "corpus_rows": corpus_info["rows"],
                   "wait_median_ms": float(np.median(waits)) * 1e3, **served}
    if shuffled:
        out["info"].update(locator_built=loc_info["built"], locator_s=loc_info["locator_s"])
    out["checks"] = checks
    return out
