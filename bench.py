"""Benchmark: libsvm parse-to-HBM GB/s/chip — the headline driver metric.

Measures the full single-chip pipeline on this host's accelerator:
criteo-shaped libsvm (one shard — per-chip throughput is the metric;
the multi-part/multi-host shard shape is bench_suite config 4, which
runs all parts with concurrent pipelines) → native C++ parse → zero-copy
CSR views → async jax.device_put into device memory, transfers riding
under parse via detached leases.

The measured config is BUILT from the declarative pipeline graph
(dmlc_tpu.pipeline): ``from_uri(...).parse(...).batch(pad=True)
.to_device(...)`` compiles to the parser + ABI-5 native batch assembly
(bucket-padded device-layout batches emitted straight from the parse
arena — ``assembly_path`` says which rung served) + windowed async
transfers through the reusable host staging pair, with a telemetry
probe at each stage boundary and the in-flight device window owned by
the between-epoch autotuner instead of a hard-coded constant.
``DMLC_TPU_BENCH_ASSEMBLY=none`` restores the pre-r7 raw-block config
for before/after attribution. A short hand-wired
reference run (DMLC_TPU_BENCH_HANDWIRED_EPOCHS, default 3) reports
"handwired_gbps" alongside so pipeline overhead stays visible.

CLI: ``python bench.py [--trace out.json]`` — with --trace the
measurement epochs run under the dmlc_tpu.obs trace recorder and a
Chrome/Perfetto trace-event JSON (per-stage pull spans, queue waits,
transfer drains, native-engine counter tracks) lands at the given path.

Prints exactly ONE JSON line: {"metric", "value", "unit",
"vs_baseline", "best_epoch", "epochs", "bound", "assembly_path",
"assemble_wait_s", "parse_cpu_gbps_core",
"sustained_gauge_ok", "gauge_ok_epochs", "gauge_ok_threshold",
"epoch_gauges", "gauge_bands", "run_band", "replay_gbps", "replay",
"replay_tier", "handwired_gbps", "pipeline", "metrics", "analysis",
"control", "trace"} —
"value" is the SUSTAINED rate (20%-trimmed mean of per-epoch GB/s over
>= 5 epochs / >= the time budget), "best_epoch" the fastest single
epoch, "parse_cpu_gbps_core" the thread-CPU parse rate (immune to this
burstable VM's credit scheduler), "sustained_gauge_ok" the same
trimmed mean restricted to epochs whose pre-epoch host-memcpy gauge
cleared "gauge_ok_threshold" (credit-healthy epochs only — the
cross-run-comparable number; per-epoch gauges ride in "epoch_gauges"),
"gauge_bands" the same statistic split per comparability class
(BASELINE.md's credit-recovery bands: drained < 1.0, plateau 1.0-1.6,
elevated 1.6-3.0, full >= 3.0 GB/s memcpy) with "run_band" the run's
modal band — numbers from runs on different credit days compare within
a band without rerunning, "replay" the parse-once/replay-epochs page
probe (>= 3 gauge-tagged replay epochs: replay_best / replay_sustained
text-equivalent GB/s + build cost; "replay_gbps" keeps the best rate
for older readers; "value" deliberately excludes replay),
"replay_tier" the page-SPILL steady-replay probe (ShardedRowBlockIter
forced over its cache budget: parse-epoch vs page-replay-epoch rates
and their speedup — the ISSUE-2 acceptance number), "bound" whether
the best epoch waited mainly on transfers or on parse, "pipeline" the
best epoch's per-stage stats snapshot + the autotune report, "metrics"
the obs metrics-registry snapshot taken at the best epoch (queue
collectors, engine counters, profiler aggregates — the versioned
obs.metrics schema), "trace" the --trace output path (null without
--trace), and vs_baseline is value / 2.0 (the BASELINE.json target of
2 GB/s/chip; the reference publishes no numbers of its own, see
BASELINE.md).

Secondary diagnostics go to stderr.
"""

import json
import os
import sys
import tempfile
import time

DATA = os.path.join(tempfile.gettempdir(), "dmlc_tpu_bench.libsvm")
TARGET_GBPS = 2.0
SIZE_MB = int(os.environ.get("DMLC_TPU_BENCH_MB", "256"))


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def ensure_data(path: str = DATA, size_mb: int = SIZE_MB) -> int:
    """Write a criteo-shaped libsvm corpus of ~size_mb MB to ``path``:
    a 4000-row block made from seed 0 and repeated, 25-44 features per
    row over a 10^6 index space, labels 1 with probability 1/4 (about
    Criteo's click rate, so a model's bias has a gradient to follow).
    Written anew on every call (well under a second for 256 MB), to a
    temp file renamed over ``path``: whatever was at ``path`` before,
    from this generator or another, is never reused."""
    import numpy as np
    want = size_mb << 20
    rng = np.random.default_rng(0)
    rows = []
    for _ in range(4000):
        nnz = int(rng.integers(25, 45))
        idx = np.sort(rng.choice(10 ** 6, nnz, replace=False))
        vals = rng.random(nnz)
        rows.append(" ".join(f"{j}:{v:.6f}" for j, v in zip(idx, vals)))
    labels = rng.random(len(rows)) < 0.25
    block = "".join(f"{int(y)} {r}\n" for y, r in zip(labels, rows)).encode()
    reps = max(1, want // len(block))
    tmp = f"{path}.tmp{os.getpid()}"
    with open(tmp, "wb") as f:
        for _ in range(reps):
            f.write(block)
    os.replace(tmp, path)
    return os.path.getsize(path)


def require_tpu():
    """The measured device, or exit: a rate taken anywhere but on the
    chip is not this benchmark's number."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        log(f"no TPU: JAX found {dev.platform} ({dev}); refusing to "
            "measure")
        sys.exit(3)
    return dev


def main() -> None:
    # --trace out.json: validated FIRST — a missing path must fail in
    # milliseconds, not after minutes of warmup epochs
    trace_path = None
    if "--trace" in sys.argv:
        i = sys.argv.index("--trace")
        if i + 1 >= len(sys.argv):
            log("--trace requires an output path")
            sys.exit(2)
        trace_path = sys.argv[i + 1]
    dev = require_tpu()
    from dmlc_tpu import native
    from dmlc_tpu.utils.compile_cache import place_compile_cache
    log(f"compile cache: {place_compile_cache()}")
    native.get_lib()  # built from engine.cc on first use, or raises
    size = ensure_data()
    # live telemetry opt-ins (no-ops without their env vars): with
    # DMLC_TPU_SERVE_PORT set the measurement epochs are scrapeable
    # (curl :PORT/metrics) while they run; with DMLC_TPU_FLIGHT_DIR a
    # crash mid-bench leaves a post-mortem bundle
    from dmlc_tpu.obs.aggregate import install_if_env as gang_if_env
    from dmlc_tpu.obs.flight import install_if_env
    from dmlc_tpu.obs.serve import serve_if_env
    from dmlc_tpu.obs.timeseries import install_if_env as history_if_env
    srv = serve_if_env()
    if srv is not None:
        log(f"obs status server: http://127.0.0.1:{srv.port}/metrics")
    # history BEFORE flight: flight joins an existing ring but installs
    # its own 15 s one when none is running — the operator's
    # DMLC_TPU_HISTORY_S/_BYTES must win
    history_if_env()  # DMLC_TPU_HISTORY_S: /history + bundle history
    install_if_env()
    gang_if_env()     # DMLC_TPU_GANG_POLL_S (rank 0): /gang timeline
    # the sampling profiler is DEFAULT-ON for bench runs (env still
    # wins: DMLC_TPU_PROFILE_HZ sets the rate, =0 disables): the
    # embedded "analysis" verdict then carries hot_frames — which
    # FUNCTION the bound stage burns in, not just which stage
    from dmlc_tpu.obs import profile as _profile
    if _profile.install_if_env() is None \
            and os.environ.get(_profile.ENV_PROFILE_HZ) is None:
        _profile.install()
    # the verdict-driven controller is DEFAULT-ON for bench runs (env
    # wins: DMLC_TPU_CONTROL=0 disables): the measurement pipeline's
    # knobs move against the /analyze verdict instead of the blind
    # hill-climber, and every decision lands in the ledger embedded
    # under "control" — campaigns record WHAT moved and WHY
    from dmlc_tpu.obs import control as _ctl
    if _ctl.install_if_env() is None \
            and os.environ.get(_ctl.ENV_CONTROL) is None:
        _ctl.install()
    import jax
    import numpy as np
    from dmlc_tpu.data.parser import Parser

    log(f"device: {dev} platform={dev.platform} kind={dev.device_kind}")
    log(f"data: {size / 1e6:.1f} MB, engine=native")

    # warmup (compile/caches)
    warm = Parser.create(DATA, 0, 64, format="libsvm",
                         engine="native")
    warm.next()
    b = warm.value()
    jax.block_until_ready(jax.device_put(b.offset, dev))
    if hasattr(warm, "destroy"):
        warm.destroy()

    # 4 MB parse chunks: the size of every chip record so far (r1-r5,
    # taken through a shared-chip transfer path, not a plain v5e host);
    # not yet re-measured on the v5e
    chunk_mb = int(os.environ.get("DMLC_TPU_BENCH_CHUNK_MB", "4"))

    # Hand-wired reference config (pre-r6 measurement loop): parser →
    # fixed 4-deep async device_put window with leased arenas. Run a
    # few epochs of it so the pipeline-built path below stays honest.
    def handwired_epoch(parser):
        parser.before_first()
        t0 = time.perf_counter()
        in_flight = []  # (future, lease): lease released after transfer
        while parser.next():
            block = parser.value()
            lease = parser.detach() if hasattr(parser, "detach") else None
            in_flight.append((jax.device_put(
                {"offset": block.offset, "label": block.label,
                 "index": block.index, "value": block.value}, dev), lease))
            if len(in_flight) > 4:
                fut, ls = in_flight.pop(0)
                jax.block_until_ready(fut)
                if ls is not None:
                    ls.release()
        for fut, ls in in_flight:
            jax.block_until_ready(fut)
            if ls is not None:
                ls.release()
        return time.perf_counter() - t0

    handwired_gbps = None
    hw_epochs = int(os.environ.get("DMLC_TPU_BENCH_HANDWIRED_EPOCHS", "3"))
    if hw_epochs > 0:
        hw_parser = Parser.create(DATA, 0, 1, format="libsvm",
                                  engine="native", chunk_size=chunk_mb << 20)
        hw_walls = [handwired_epoch(hw_parser) for _ in range(hw_epochs)]
        if hasattr(hw_parser, "destroy"):
            hw_parser.destroy()
        handwired_gbps = round(size / min(hw_walls) / 1e9, 4)
        log(f"hand-wired reference: best of {hw_epochs} epochs = "
            f"{handwired_gbps} GB/s")

    # The measured config, built from the declarative graph: same
    # parser, same windowed async transfer — but probed per stage and
    # with the in-flight window an autotuner knob instead of the
    # constant 4 the hand-wired loop carried. Since r7 the steady path
    # also ASSEMBLES: batch(pad=True) emits fixed-shape device-layout
    # batches, fused into the engine's ABI-5 native assembly when the
    # native parser serves (assembly_path="native-padded"; the Python
    # fused golden otherwise), and to_device routes them through the
    # host staging pair so transfer N overlaps assembly N+1.
    # DMLC_TPU_BENCH_ASSEMBLY=none restores the pre-r7 raw-block
    # config for before/after attribution.
    from dmlc_tpu.pipeline import Pipeline
    assembly_mode = os.environ.get("DMLC_TPU_BENCH_ASSEMBLY", "auto")
    # DMLC_TPU_BENCH_SHARDS=N (N>1): split the ONE bench file across N
    # native parsers on aligned byte ranges (ISSUE 7 rung c) — the
    # single-file workload parallelizes its reader/parse stages like a
    # multi-file one, byte-identical ordering pinned by tests. Padded
    # assembly over a sharded parse runs the python-fused rung (a
    # padded batch may not straddle the shard boundary), so this knob
    # trades the native-assembly rung for read/parse parallelism —
    # the right trade whenever cores outnumber the one reader thread.
    shards = int(os.environ.get("DMLC_TPU_BENCH_SHARDS", "0") or 0)
    parse_kw = {"shards": shards} if shards > 1 else {}
    pl = (Pipeline.from_uri(DATA)
          .parse(format="libsvm", engine="native",
                 chunk_size=chunk_mb << 20, **parse_kw))
    if assembly_mode != "none":
        rows_pb = int(os.environ.get("DMLC_TPU_BENCH_BATCH_ROWS",
                                     str(8 << 10)))
        # worst-case nnz bound: ensure_data rows carry < 45 features
        nnz_pb = int(os.environ.get("DMLC_TPU_BENCH_NNZ_BUCKET",
                                    str(rows_pb * 45)))
        pl = pl.batch(rows_pb, pad=True, nnz_bucket=nnz_pb)
    built = pl.to_device(dev, window="auto").build(autotune=True)

    def epoch():
        for _ in built:
            pass
        snap = built.stats()
        parse_st = snap["stages"][0]
        dev_st = snap["stages"][-1]
        t_pull = parse_st["wait_s"]
        dx = dev_st.get("extra") or {}
        t_xfer = dx.get("xfer_wait_s", 0.0)
        # assemble-wait: pad+stack memcpy seconds this epoch — the
        # engine's consumer-side assemble_ns on the fused native rung
        # (where parse+assemble are ONE stage), the measured pad_single
        # time on the python rung (its own stage), plus the host
        # staging copies (to_device.stage spans) when staging runs.
        # Scanned across stages: the fused path folds assembly into
        # stages[0], the fallback carries it on its own stage.
        t_asm = dx.get("staging_assemble_s", 0.0)
        stats = None
        for st in snap["stages"]:
            x = st.get("extra") or {}
            t_asm += x.get("assemble_s", 0.0)
            if stats is None:
                stats = x.get("engine")
        return (snap["wall_s"], t_pull, t_xfer, t_asm, parse_st["rows"],
                parse_st["nnz"], stats, snap)

    # Sustained measurement (VERDICT r2 #2): run at least min_epochs
    # passes AND keep sampling for the full time budget, then report the
    # TRIMMED MEAN as the headline — a number that survives a cold re-run
    # on this burstable host — with the best epoch alongside as the
    # hardware-capability ceiling. (min_epochs >= 3 guarantees the byte
    # budget is >= 3x the data size.)
    budget_s = float(os.environ.get("DMLC_TPU_BENCH_BUDGET_S", "60"))
    min_epochs = max(3, int(os.environ.get("DMLC_TPU_BENCH_MIN_EPOCHS", "5")))
    # DMLC_TPU_TRACE=<dir>: dump a jax.profiler device timeline of one
    # epoch (the program's spans ride on it) for offline inspection
    trace_dir = os.environ.get("DMLC_TPU_TRACE")
    if trace_dir:
        with jax.profiler.trace(trace_dir):
            epoch()
        log(f"jax.profiler trace written to {trace_dir}")

    # --trace (parsed at the top of main): record the measurement
    # epochs with the obs trace recorder and export Chrome/Perfetto
    # trace-event JSON — per-stage pull spans, queue waits, transfer
    # drains, and the native engine's counters as counter tracks
    from dmlc_tpu.obs import metrics as obs_metrics
    from dmlc_tpu.obs import trace as obs_trace
    if trace_path:
        obs_trace.start()

    # Every epoch is tagged with a host-memcpy credit gauge (~50 ms,
    # VERDICT r4 #5): this burstable VM's CPU credits swing wall rates
    # ~10x, and without the per-epoch gauge a reader cannot separate
    # "slow framework epoch" from "drained credit bucket". Epochs whose
    # gauge clears GAUGE_OK_GBPS feed sustained_gauge_ok.
    from dmlc_tpu.bench_transfer import memcpy_gauge
    GAUGE_OK_GBPS = float(os.environ.get("DMLC_TPU_BENCH_GAUGE_OK", "1.0"))
    times = []   # (wall_s, gauge_gbps) per epoch
    best = None
    best_stats = None
    best_waits = (0.0, 0.0, 0.0)
    best_snap = None
    best_metrics = None
    t_start = time.perf_counter()
    i = 0
    while True:
        gauge = memcpy_gauge()
        if _ctl.active() is not None:
            # the controller judges the climate from the same gauge
            # the bands are built on — a drained bucket FREEZES knobs
            _ctl.active().note_gauge(gauge)
        dt, t_pull, t_xfer, t_asm, rows, nnz, stats, snap = epoch()
        times.append((dt, gauge))
        log(f"epoch {i}: rows={rows} nnz={nnz} wall={dt:.2f}s "
            f"pull-wait={t_pull:.2f}s xfer-wait={t_xfer:.2f}s "
            f"assemble-wait={t_asm:.2f}s "
            f"gauge={gauge:.2f} -> {size / dt / 1e9:.3f} GB/s")
        if best is None or dt < best:
            best, best_stats = dt, stats
            best_waits = (t_pull, t_xfer, t_asm)
            best_snap = snap
            # the registry snapshot AT the best epoch: queue
            # collectors, engine counters, profiler aggregates — the
            # versioned obs.metrics schema, embedded in BENCH JSON
            best_metrics = obs_metrics.REGISTRY.snapshot()
        i += 1
        elapsed = time.perf_counter() - t_start
        if i >= min_epochs and elapsed > budget_s:
            break
    if trace_path:
        rec = obs_trace.stop()
        if rec is not None:
            from dmlc_tpu.obs.export import write_chrome
            write_chrome(rec, trace_path)
            log(f"obs trace: {len(rec.events())} events "
                f"({rec.dropped} dropped) -> {trace_path}")
    # 20%-per-side trimmed mean of per-epoch rates: robust to both burst
    # windows and throttle windows of the credit scheduler

    def trimmed_mean(vals):
        vals = sorted(vals)
        k = len(vals) // 5
        cut = vals[k:len(vals) - k]
        return sum(cut) / len(cut)

    sustained = trimmed_mean([size / t / 1e9 for t, _ in times])
    # the same statistic over credit-healthy epochs only: the number a
    # judge can compare across runs without rerunning on a better day
    ok_rates = [size / t / 1e9 for t, g in times if g >= GAUGE_OK_GBPS]
    sustained_gauge_ok = (round(trimmed_mean(ok_rates), 4)
                          if len(ok_rates) >= 3 else None)
    log(f"gauge-ok epochs: {len(ok_rates)}/{len(times)} "
        f"(threshold {GAUGE_OK_GBPS} GB/s memcpy)")

    # Band-split sustained rates (BASELINE.md "Credit-recovery
    # profile"): the memcpy gauge separates comparability classes —
    # drained (< 1.0), the post-recovery plateau (1.0-1.6), elevated
    # (1.6-3.0) and full-bucket (>= 3.0, a long-rested VM). Numbers
    # compare ACROSS runs only within one band; the run's modal band is
    # stamped so two BASELINE rows can be read side by side without
    # rerunning either. The band cut points live in obs.analyze (the
    # compare/attribution engine reads the same ones).
    from dmlc_tpu.obs.analyze import gauge_band

    band_rates = {}
    for t, g in times:
        band_rates.setdefault(gauge_band(g), []).append(size / t / 1e9)
    gauge_bands = {
        band: {"epochs": len(rates),
               # same >= 3-epoch rule as sustained_gauge_ok: fewer make
               # a trimmed mean meaningless
               "sustained": (round(trimmed_mean(rates), 4)
                             if len(rates) >= 3 else None)}
        for band, rates in sorted(band_rates.items())}
    run_band = max(band_rates, key=lambda b: len(band_rates[b]))
    log(f"gauge bands: " + ", ".join(
        f"{b}={v['epochs']}ep"
        + (f"@{v['sustained']}" if v["sustained"] else "")
        for b, v in gauge_bands.items()) + f"; run_band={run_band}")
    if best_stats:
        # per-stage breakdown (VERDICT r1 #7): where the best epoch's
        # time went (shared formatter with the bench suite)
        from dmlc_tpu.bench_suite import format_stages
        line = format_stages(best_stats, size)
        if line:
            log(line)
    autotune_report = built.autotune_report()
    if _ctl.active() is not None:
        # the controller subsumed the autotuner: knob moves belong to
        # the "control" ledger below — reporting them as autotuner
        # work would credit a tuner that never ran
        autotune_report = None
    built.close()
    if autotune_report:
        log(f"autotune: values={autotune_report['values']} "
            f"tuned={autotune_report['tuned']} "
            f"decisions={len(autotune_report['decisions'])}")

    # Page-replay rate (VERDICT r4 #2, defensible since r6): the
    # repeated-epoch training shape — parse once into binary pages,
    # replay pages → HBM on every later epoch (DiskRowIter;
    # ShardedRowBlockIter replays retained rounds the same way). >= 3
    # replay epochs, each gauge-tagged, with best AND sustained
    # reported: a single post-drain epoch undersold config 8 by ~5x
    # (r5 measured replay_gbps 0.26 vs config 8's 1.4-2.0). Reported
    # ALONGSIDE the headline: "value" stays the true parse rate,
    # replay must not inflate it.
    replay_gbps = None
    replay = None
    if os.environ.get("DMLC_TPU_BENCH_REPLAY", "1") != "0":
        try:
            from dmlc_tpu.bench_suite import bench_page_replay
            rp_epochs = int(os.environ.get("DMLC_TPU_BENCH_REPLAY_EPOCHS",
                                           "5"))
            rp = bench_page_replay(min(SIZE_MB, 64), epochs=rp_epochs,
                                   gauge_fn=memcpy_gauge)
            # unrounded-wall rates from the suite (the display-rounded
            # epoch_walls would quantize ~30 ms epochs by percents)
            rp_rates = rp["epoch_rates_text_gbps"]
            replay_gbps = rp["text_equiv_gbps"]  # best epoch
            replay = {
                "replay_best": replay_gbps,
                "replay_sustained": round(trimmed_mean(rp_rates), 4),
                "epoch_walls": rp["epoch_walls"],
                "epoch_gauges": rp["epoch_gauges"],
                "build_s": rp["build_s"],
                "page_gbps": round(rp["gbps"], 4),
            }
            log(f"page replay: best {replay_gbps} / sustained "
                f"{replay['replay_sustained']} GB/s text-equivalent "
                f"over {len(rp_rates)} epochs (gauges "
                f"{rp['epoch_gauges']}, build {rp['build_s']}s)")
        except Exception as e:  # noqa: BLE001 — diagnostics must not
            log(f"page replay measurement failed: {e}")  # kill the run

    # Page-SPILL steady replay (r6 tentpole, the ISSUE-2 acceptance
    # probe): a config-7-style iterator forced over its cache budget —
    # steady epochs must serve from spilled round pages at >= 2x the
    # parse-epoch rate.
    replay_tier = None
    if os.environ.get("DMLC_TPU_BENCH_SPILL", "1") != "0":
        try:
            from dmlc_tpu.bench_suite import bench_spill_replay
            sr = bench_spill_replay(min(SIZE_MB, 64),
                                    gauge_fn=memcpy_gauge)
            replay_tier = {
                "mode": sr["mode"],
                "parse_epoch_gbps": sr["parse_epoch_gbps"],
                "parse_epoch_gauge": sr["parse_epoch_gauge"],
                "spill_epoch_gbps": sr["spill_epoch_gbps"],
                "replay_gbps": round(sr["gbps"], 4),
                "replay_sustained_gbps": sr["replay_sustained_gbps"],
                "speedup_vs_parse": sr["speedup_vs_parse"],
                "epoch_gauges": sr["epoch_gauges"],
                "rounds": sr["rounds"],
            }
            log(f"page-spill steady replay: {sr['gbps']:.3f} GB/s "
                f"text-equivalent vs {sr['parse_epoch_gbps']} parse "
                f"({sr['speedup_vs_parse']}x, tier={sr['mode']})")
        except Exception as e:  # noqa: BLE001 — diagnostics must not
            log(f"page-spill replay measurement failed: {e}")

    best_gbps = size / best / 1e9
    # Credit-immune kernel rate (VERDICT r3 #4): thread-CPU time spent
    # parsing, immune to this burstable VM's credit scheduler and to
    # the consumer thread preempting workers on a 1-core host.
    parse_cpu_gbps = None
    if best_stats and best_stats.get("parse_cpu_ns"):
        parse_cpu_gbps = size / best_stats["parse_cpu_ns"]
    # Which side bounds the pipeline (VERDICT r3 #1): the consumer
    # either waits on the parser (parse-bound) or on device transfers
    # (transfer-bound).
    pull_s, xfer_s, asm_s = best_waits
    bound = "transfer" if xfer_s > pull_s else "parse"
    # which rung assembled the measured batches: "native-padded"
    # (engine ABI-5), "python-fused" (pad_single golden) or "none"
    # (DMLC_TPU_BENCH_ASSEMBLY=none, the pre-r7 raw-block config)
    assembly_path = "none"
    if best_snap:
        assembly_path = next(
            (x["assembly_path"] for s in best_snap["stages"]
             if (x := s.get("extra") or {}).get("assembly_path")),
            "none")
    log(f"sustained (trimmed mean of {len(times)} epochs) = "
        f"{sustained:.3f} GB/s; best epoch = {best_gbps:.3f} GB/s; "
        f"bound={bound} (pull-wait {pull_s:.2f}s vs xfer-wait "
        f"{xfer_s:.2f}s vs assemble-wait {asm_s:.2f}s in best epoch); "
        f"assembly_path={assembly_path}")
    # The structured attribution verdict (obs.analyze): the best
    # epoch's stage waits + the registry snapshot + the run's credit
    # gauges, decomposed into a schema-pinned bound/evidence block —
    # every campaign self-attributes instead of waiting for a human to
    # read the stage numbers
    analysis = None
    if best_snap:
        from dmlc_tpu.obs.analyze import attribute
        analysis = attribute(best_snap, metrics=best_metrics,
                             epoch_gauges=[g for _, g in times],
                             run_band=run_band)
        log(f"analysis: bound={analysis['bound']} "
            f"({analysis['confidence']}) — "
            + "; ".join(analysis["evidence"][:3]))
    control_doc = None
    if _ctl.active() is not None:
        try:
            control_doc = _ctl.active().to_dict(last=32)
        except Exception as e:  # noqa: BLE001 — the campaign line
            log(f"control ledger excerpt failed: {e}")  # must survive
    print(json.dumps({
        "metric": "libsvm_parse_to_hbm_throughput",
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "host_cores": os.cpu_count(),
        "value": round(sustained, 4),
        "unit": "GB/s/chip",
        "vs_baseline": round(sustained / TARGET_GBPS, 4),
        "best_epoch": round(best_gbps, 4),
        "epochs": len(times),
        "bound": bound,
        # which rung assembled the measured batches (r7): attributes
        # campaign wins to native-padded vs python-fused vs the pre-r7
        # raw-block config; assemble_wait_s is the best epoch's
        # pad+stack memcpy seconds (engine assemble_ns or pad_single
        # time, plus host staging copies)
        "assembly_path": assembly_path,
        "assemble_wait_s": round(asm_s, 4),
        # null when the engine exposes no thread-CPU stats (python
        # fallback) — the key is always present for consumers
        "parse_cpu_gbps_core": (round(parse_cpu_gbps, 4)
                                if parse_cpu_gbps is not None else None),
        # trimmed mean over epochs whose pre-epoch host-memcpy gauge
        # cleared the threshold — separates framework throughput from
        # this burstable VM's credit bucket; null when <3 such epochs
        "sustained_gauge_ok": sustained_gauge_ok,
        "gauge_ok_epochs": len(ok_rates),
        "gauge_ok_threshold": GAUGE_OK_GBPS,
        "epoch_gauges": [round(g, 2) for _, g in times],
        # per-comparability-class sustained rates + this run's modal
        # band (BASELINE.md credit-recovery bands): cross-run reads
        # compare within a band only
        "gauge_bands": gauge_bands,
        "run_band": run_band,
        # parse-once/replay-epochs rate in text-equivalent GB/s (the
        # repeated-epoch training shape); null if the probe failed.
        # replay_gbps keeps the BEST single epoch (older readers);
        # "replay" carries best + sustained + per-epoch gauges/walls
        "replay_gbps": replay_gbps,
        "replay": replay,
        # page-SPILL steady replay: the over-budget iterator serving
        # steady epochs from spilled round pages (mode/rates/speedup);
        # null if the probe failed
        "replay_tier": replay_tier,
        # the pre-r6 hand-wired loop's best-of-N reference (null when
        # DMLC_TPU_BENCH_HANDWIRED_EPOCHS=0): the pipeline-built path
        # above must not sit below it
        "handwired_gbps": handwired_gbps,
        # the pipeline-built config's best epoch, per stage (schema:
        # dmlc_tpu.pipeline.stats) + the between-epoch autotune report
        # — null when the verdict-driven controller owned the knobs
        # instead (its moves ride the "control" ledger below)
        "pipeline": {
            "stages": best_snap["stages"] if best_snap else None,
            "knobs": best_snap["knobs"] if best_snap else None,
            "autotune": autotune_report,
        },
        # obs metrics-registry snapshot taken at the best epoch
        # (schema: dmlc_tpu.obs.metrics.METRICS_SCHEMA)
        "metrics": best_metrics,
        # the bottleneck-attribution verdict over the best epoch
        # (schema: dmlc_tpu.obs.analyze.VERDICT_KEYS, lint-pinned):
        # bound/band/confidence/evidence/stage_waits — what obsctl
        # diagnose prints and the /analyze endpoint serves live
        "analysis": analysis,
        # the control plane's decision-ledger excerpt (schema:
        # dmlc_tpu.obs.control.CONTROL_SCHEMA): which knobs moved,
        # on which verdicts, with the evidence — what /control serves
        # live and obsctl control renders; null when the controller
        # was disabled (DMLC_TPU_CONTROL=0) or its payload failed
        # (to_dict runs knob closures; a raising one must not cost
        # the whole campaign line — the flight.py discipline)
        "control": control_doc,
        # Chrome/Perfetto trace of the measurement epochs (--trace)
        "trace": trace_path,
    }))


if __name__ == "__main__":
    main()
