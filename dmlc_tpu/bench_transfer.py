"""Host->HBM transfer probe: maps the device_put ceiling on this host.

VERDICT r3 #1 asked whether N concurrent transfer streams can aggregate
past the single-stream host->device rate (r3 measured 1.28 GB/s median
at 4 MB chunks) toward the 2 GB/s/chip north star. This probe answers
it with an interleaved measurement matrix. r4 ran it through a
shared-chip transfer path — a remote client in front of a v5e, not a
plain v5e host — so what it found describes that path, and none of it
has been re-measured on the v5e (full numbers: BASELINE.md "Transfer
ceiling"):

- Fresh-state single stream (1-4 MB chunks, lookahead 2, ONE thread)
  reached 1.5-1.7 GB/s median, 2.1 GB/s best cell.
- N threads driving concurrent streams were NEGATIVE, not additive:
  2-4 threads x 4 MB measured ~0.17 GB/s vs 1.69 single-stream in the
  same windows (concurrent device_put calls contended in that path's
  client). device_chunks/bench.py use one transfer stream.
- After ~1-2 GB streamed back-to-back, all shapes collapsed to
  ~0.1-0.4 GB/s and recovered with idle time: rate shaping in that
  path, independent of the VM's CPU credits (the collapse coincided
  with 5.3 GB/s host memcpy, and 1.5-1.7 GB/s transfers held while
  memcpy was throttled to 0.19 GB/s).
- Transfers overlap host compute: ~0.7 GB/s transfer concurrent with
  5.5 GB/s of host memcpy on the same core (the "cpu_share"~100% of
  a blocked stream is block_until_ready spin-wait, not real work), so
  parse and transfer do not steal from each other.
- Monolithic 64 MB puts and 8 MB chunks are never better and often
  worse; 1-4 MB chunks are flat in matched windows. 4 MB stays the
  default.

Usage: python -m dmlc_tpu.bench_transfer [--reps N] [--mb MB]
Prints a per-cell median table to stderr and ONE JSON line to stdout:
{"cells": {name: gbps}, "memcpy_gbps": g, "cpu_share": s} — rerunnable
evidence for the ceiling documented in BASELINE.md. Cells interleave
and each round logs the memcpy gauge so credit states can be matched
across runs; trust per-round comparisons and best cells over
cross-round medians when the gauge swings.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import threading
import time
from typing import Callable, Dict, List


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def memcpy_gauge(mb: int = 48) -> float:
    """Host memcpy GB/s — the CPU credit-state indicator. Transfer cells
    are only comparable across runs at similar gauge readings."""
    import numpy as np
    a = np.full(mb << 20, 7, np.uint8)
    b = np.empty_like(a)
    t0 = time.perf_counter()
    np.copyto(b, a)
    return a.nbytes / (time.perf_counter() - t0) / 1e9


def _one_stream(dev, chunk: int, lookahead: int, nchunks: int,
                bufs) -> None:
    import jax
    pending: List = []
    for i in range(nchunks):
        pending.append(jax.device_put(bufs[i % len(bufs)], dev))
        if len(pending) > lookahead:
            jax.block_until_ready(pending.pop(0))
    for p in pending:
        jax.block_until_ready(p)


def cell_single(dev, chunk_mb: int, lookahead: int, total_mb: int) -> float:
    """One thread, ring of reused buffers, `lookahead` puts in flight —
    the device_chunks shape (io/tpu_fs.py)."""
    import numpy as np
    chunk = chunk_mb << 20
    n = max(1, (total_mb << 20) // chunk)
    bufs = [np.full(chunk, 7, np.uint8) for _ in range(lookahead + 1)]
    t0 = time.perf_counter()
    _one_stream(dev, chunk, lookahead, n, bufs)
    return n * chunk / (time.perf_counter() - t0) / 1e9


def cell_threads(dev, nthreads: int, chunk_mb: int, lookahead: int,
                 total_mb: int) -> float:
    """N threads each driving an independent pooled stream — the
    aggregation question from VERDICT r3 #1."""
    import numpy as np
    chunk = chunk_mb << 20
    n_per = max(1, (total_mb << 20) // chunk // nthreads)
    all_bufs = [[np.full(chunk, 7, np.uint8) for _ in range(lookahead + 1)]
                for _ in range(nthreads)]
    barrier = threading.Barrier(nthreads + 1)

    def work(bufs):
        barrier.wait()
        _one_stream(dev, chunk, lookahead, n_per, bufs)

    ts = [threading.Thread(target=work, args=(all_bufs[i],), daemon=True)
          for i in range(nthreads)]
    for t in ts:
        t.start()
    barrier.wait()
    t0 = time.perf_counter()
    for t in ts:
        t.join()
    return nthreads * n_per * chunk / (time.perf_counter() - t0) / 1e9


def cell_mono(dev, size_mb: int) -> float:
    """One monolithic device_put — per-call overhead amortized away."""
    import numpy as np
    a = np.full(size_mb << 20, 7, np.uint8)
    t0 = time.perf_counter()
    import jax
    jax.block_until_ready(jax.device_put(a, dev))
    return (size_mb << 20) / (time.perf_counter() - t0) / 1e9


def cell_under_cpu_load(dev, chunk_mb: int = 4, lookahead: int = 2,
                        total_mb: int = 48):
    """Transfer stream while a host thread burns CPU on memcpy (a parse
    stand-in): returns (transfer GB/s, concurrent memcpy GB/s). Both
    staying high demonstrates parse/transfer overlap."""
    import numpy as np
    stop = threading.Event()
    a = np.full(8 << 20, 3, np.uint8)
    b = np.empty_like(a)
    copied = [0]

    def burn():
        while not stop.is_set():
            np.copyto(b, a)
            copied[0] += a.nbytes

    t = threading.Thread(target=burn, daemon=True)
    t.start()
    t0 = time.perf_counter()
    rate = cell_single(dev, chunk_mb, lookahead, total_mb)
    dt = time.perf_counter() - t0
    stop.set()
    t.join()
    return rate, copied[0] / dt / 1e9


def enqueue_cpu_share(dev, chunk_mb: int = 4, total_mb: int = 64) -> float:
    """Fraction of transfer wall time spent as client process CPU.
    Caution: block_until_ready SPIN-WAITS, so ~1.0 here does NOT mean
    the core is the ceiling — read it together with cell_under_cpu_load
    (r4: transfers sustained 1.5+ GB/s with host memcpy throttled to
    0.19 GB/s, so the wire path costs little real host CPU)."""
    import numpy as np
    import jax
    chunk = chunk_mb << 20
    n = max(1, (total_mb << 20) // chunk)
    bufs = [np.full(chunk, 7, np.uint8) for _ in range(3)]
    w0, c0 = time.perf_counter(), time.process_time()
    _one_stream(dev, chunk, 2, n, bufs)
    wall = time.perf_counter() - w0
    cpu = time.process_time() - c0
    return cpu / wall if wall > 0 else 0.0


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=5,
                    help="interleaved repetitions per cell (median reported)")
    ap.add_argument("--mb", type=int, default=64,
                    help="bytes per cell per rep (MB)")
    args = ap.parse_args()

    import jax
    dev = jax.devices()[0]
    log(f"device: {dev} platform={dev.platform}")
    import numpy as np
    jax.block_until_ready(jax.device_put(np.zeros(1 << 20, np.uint8), dev))

    mb = args.mb
    cells: Dict[str, Callable[[], float]] = {
        "single-1MB": lambda: cell_single(dev, 1, 2, mb),
        "single-2MB": lambda: cell_single(dev, 2, 2, mb),
        "single-4MB": lambda: cell_single(dev, 4, 2, mb),
        "single-8MB": lambda: cell_single(dev, 8, 2, mb),
        "threads2-4MB": lambda: cell_threads(dev, 2, 4, 2, mb),
        "threads4-4MB": lambda: cell_threads(dev, 4, 4, 2, mb),
        "threads4-1MB": lambda: cell_threads(dev, 4, 1, 2, mb),
        "mono-64MB": lambda: cell_mono(dev, 64),
    }
    # cells interleave (one rep of every cell per round) so a credit
    # swing mid-run biases all cells equally, and each round is tagged
    # with the memcpy gauge so readers can match credit states
    results: Dict[str, List[float]] = {k: [] for k in cells}
    gauges: List[float] = []
    for rep in range(args.reps):
        g = memcpy_gauge()
        gauges.append(g)
        for name, fn in cells.items():
            results[name].append(fn())
        log(f"round {rep}: memcpy gauge {g:.2f} GB/s")
    share = enqueue_cpu_share(dev)
    overlap_t, overlap_c = cell_under_cpu_load(dev)

    med = {k: statistics.median(v) for k, v in results.items()}
    log(f"{'cell':14s} {'median':>7s}  runs (GB/s)")
    for k, v in results.items():
        log(f"{k:14s} {med[k]:7.3f}  " +
            " ".join(f"{x:.2f}" for x in v))
    log(f"memcpy gauge median {statistics.median(gauges):.2f} GB/s; "
        f"enqueue CPU share {share:.0%}; under-cpu-load: transfer "
        f"{overlap_t:.2f} GB/s with {overlap_c:.2f} GB/s concurrent memcpy")
    print(json.dumps({
        "metric": "host_to_hbm_transfer_gbps",
        "cells": {k: round(v, 3) for k, v in med.items()},
        "memcpy_gbps": round(statistics.median(gauges), 3),
        "enqueue_cpu_share": round(share, 3),
        "overlap_transfer_gbps": round(overlap_t, 3),
        "overlap_memcpy_gbps": round(overlap_c, 3),
        "reps": args.reps,
    }))


if __name__ == "__main__":
    main()
