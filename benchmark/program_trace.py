"""The program's own spans in a traced run of a cell, set against the
device's idle time: which layer of the program holds the device idle.
The benchmark's own runs never run this.

    python3 benchmark/program_trace.py --workload <cell> --seed <n> [--seconds 50]

It runs the cell as the benchmark's traced run does (``drive.run`` with
``trace``) and reduces the same xplane a second time, before the run
removes it. The program's spans are those that carry a ``cat``
argument (``PROGRAM_ARG``), on the thread line that holds
``trace_reduce.WINDOW`` (the consumer's), clipped to the window. Per
span name: ``count``, ``seconds``, ``self_s`` (less the program spans
nested in it), ``p95_ms`` of the durations, and ``idle_s``, the
device-idle time, averaged over the chips, that overlaps the span while
it is the innermost program span open. ``program_other_idle_s`` is the
idle time with no program span open; ``program_coverage`` the share of
``bench.next_batch`` time whose innermost program span is nested inside
another one (an outermost span's own time is what no named boundary
explains). A span tagged ``end=1`` is a terminal pull, named with
``.end`` as the program's recorder names it. ``bench_wait_p95_ms`` is
the p95 of the harness's own wait for a batch (``bench.next_batch`` +
``bench.block_until_ready``) in the same window.

``figures`` reads five figures by layer from that section, under the
names a per-layer metric of each would take.

Prints one JSON line: the run's ``correct``, its metrics and device, the
section above and its figures.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from bisect import bisect_right
from collections import defaultdict
from typing import Dict, List, Optional, Tuple
from unittest import mock

import numpy as np

# run as a script, Python puts benchmark/ first on the path; the
# checkout root holds both the benchmark and the program under test
sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from benchmark import trace_reduce  # noqa: E402
from benchmark.trace_reduce import SPANS, WINDOW  # noqa: E402

PROGRAM_ARG = "cat"
# the spans of each layer, as the program names them
ASSEMBLE = ("pull/assemble", "pull/parse", "pull/cache", "pull/batch",
            "shard.parse", "shard.stack")
TRANSFER = ("to_device.stage", "to_device.put", "to_device.drain", "shard.put")
# the host's hand-over to the device, and the outermost pull it serves
PUTS = ((("to_device.stage", "to_device.put"), "pull/to_device"),
        (("shard.put",), "pull/shard"))


def reduce_profile(pd) -> Dict:
    """The program's section of a ``jax.profiler.ProfileData``. Times in
    seconds, each device figure averaged over the device planes."""
    devices = []
    window = window_line = None
    for plane in pd.planes:
        by_name = {line.name: line for line in plane.lines}
        if "XLA Ops" in by_name:
            devices.append(by_name["XLA Ops"])
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == WINDOW:
                    window, window_line = (e.start_ns, e.start_ns + e.duration_ns), line
    if window is None or not devices:
        raise ValueError(f"trace has no {WINDOW!r} span or no device plane")
    w0, w1 = window
    idle: List[Tuple[int, int]] = []  # every chip's idle intervals
    for ops in devices:
        u = trace_reduce._union([(max(e.start_ns, w0), min(e.start_ns + e.duration_ns, w1))
                                 for e in ops.events
                                 if min(e.start_ns + e.duration_ns, w1) > max(e.start_ns, w0)])
        edges = [w0] + [x for ab in u for x in ab] + [w1]
        idle += [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    spans, bench = [], defaultdict(list)
    for e in window_line.events:
        a, b = e.start_ns, e.start_ns + e.duration_ns
        if e.name in SPANS:
            bench[e.name].append((a, b))
        elif e.name != WINDOW:
            args = dict(e.stats)
            if PROGRAM_ARG in args:
                spans.append((a, b, e.name + (".end" if args.get("end") else "")))
    n = len(devices)
    out = {"window_s": (w1 - w0) / 1e9,
           "idle_s": sum(b - a for a, b in idle) / 1e9 / n}
    out.update(_program(spans, bench[SPANS[0]], idle, n, w0, w1))
    pulls = sorted(s for s in bench[SPANS[0]] if w0 <= s[0] < w1)
    readies = sorted(s for s in bench[SPANS[1]] if w0 <= s[0] < w1)
    waits = [(b - a) + (d - c) for (a, b), (c, d) in zip(pulls, readies)]
    out["bench_wait_p95_ms"] = float(np.percentile(waits, 95)) / 1e6 if waits else None
    return out


def _innermost(spans, w0, w1) -> List[Tuple[float, float, Optional[str], int]]:
    """The window cut into segments, each with the innermost program span
    open across it (None: none) and how many are open. Spans of one
    thread nest; a child that outlasts its parent by clock rounding is
    cut at the parent's end."""
    segs: List[Tuple[float, float, Optional[str], int]] = []
    stack: List[Tuple[float, str]] = []  # (end, name), innermost last
    cur = w0

    def emit(b):
        nonlocal cur
        if b > cur:
            segs.append((cur, b, stack[-1][1] if stack else None, len(stack)))
            cur = b

    for a, b, name in sorted(spans, key=lambda s: (s[0], -s[1])):
        while stack and stack[-1][0] <= a:
            emit(stack[-1][0])
            stack.pop()
        emit(a)
        stack.append((min(b, stack[-1][0]) if stack else b, name))
    while stack:
        emit(stack[-1][0])
        stack.pop()
    emit(w1)
    return segs


def _overlap(segs, intervals) -> List[float]:
    """Per segment, the time of ``intervals`` (sorted, disjoint per
    source; several sources may be merged) that falls inside it."""
    out = [0.0] * len(segs)
    starts = [s[0] for s in segs]
    for a, b in intervals:
        i = max(bisect_right(starts, a) - 1, 0)
        while i < len(segs) and segs[i][0] < b:
            lo, hi = max(a, segs[i][0]), min(b, segs[i][1])
            if hi > lo:
                out[i] += hi - lo
            i += 1
    return out


def _program(spans, pulls, idle, n, w0, w1) -> Dict:
    clipped = [(max(a, w0), min(b, w1), name) for a, b, name in spans
               if min(b, w1) > max(a, w0)]
    segs = _innermost(clipped, w0, w1)
    idle_in = _overlap(segs, idle)
    pull_in = _overlap(segs, [(max(a, w0), min(b, w1)) for a, b in pulls])
    durs: Dict[str, List[float]] = defaultdict(list)
    for a, b, name in clipped:
        durs[name].append(b - a)
    self_ns: Dict[str, float] = defaultdict(float)
    idle_ns: Dict[str, float] = defaultdict(float)
    other = covered = 0.0
    for (a, b, name, depth), i, p in zip(segs, idle_in, pull_in):
        if name is None:
            other += i
        else:
            self_ns[name] += b - a
            idle_ns[name] += i
        if depth >= 2:
            covered += p
    pulled = sum(pull_in)
    return {
        "program": {
            name: {"count": len(d), "seconds": sum(d) / 1e9,
                   "self_s": self_ns[name] / 1e9,
                   "p95_ms": float(np.percentile(d, 95)) / 1e6,
                   "idle_s": idle_ns[name] / 1e9 / n}
            for name, d in sorted(durs.items())},
        "program_other_idle_s": other / 1e9 / n,
        "program_coverage": covered / pulled if pulled else None,
    }


def reduce_dir(log_dir: str) -> Dict:
    import jax
    return reduce_profile(jax.profiler.ProfileData.from_file(
        trace_reduce.find_xplane(log_dir)))


def _idle_pct(red: Dict, spans) -> Optional[float]:
    found = [red["program"][s]["idle_s"] for s in spans if s in red["program"]]
    return 100.0 * sum(found) / red["window_s"] if found else None


def figures(red: Dict) -> Dict[str, Optional[float]]:
    """Five figures by layer; None where the trace has none of their
    spans (a program without them):

    - ``assemble.device_idle_pct``: % of the window idle under a parse or
      assembly span (``ASSEMBLE``);
    - ``transfer.device_idle_pct``: the same under a transfer span
      (``TRANSFER``);
    - ``transfer.host_ms_per_batch``: ms of the staging copy and the
      ``device_put`` (the global batch on a mesh) per outermost pull;
    - ``queue.epoch_boundary_ms``: self ms of ``pipeline.epoch_start`` and
      ``pipeline.epoch_end`` per epoch end;
    - ``queue.pull_p95_ms``: p95 of the outermost pull, the program's
      side of the harness's ``batch_wait_p95_ms``."""
    prog = red["program"]
    host = None
    for spans, pull in PUTS:
        if pull in prog and any(s in prog for s in spans):
            host = sum(prog[s]["seconds"] for s in spans if s in prog) \
                / prog[pull]["count"] * 1e3
            break
    end = prog.get("pipeline.epoch_end")
    start = prog.get("pipeline.epoch_start", {"self_s": 0.0})
    pull = next((prog[p] for _, p in PUTS if p in prog), None)
    return {
        "assemble.device_idle_pct": _idle_pct(red, ASSEMBLE),
        "transfer.device_idle_pct": _idle_pct(red, TRANSFER),
        "transfer.host_ms_per_batch": host,
        "queue.epoch_boundary_ms": (start["self_s"] + end["self_s"]) / end["count"] * 1e3
        if end else None,
        "queue.pull_p95_ms": pull["p95_ms"] if pull else None,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=50.0)
    args = ap.parse_args(argv)
    from benchmark import drive
    reduce_harness = trace_reduce.reduce_dir
    section: Dict = {}

    def both(log_dir):
        section.update(reduce_dir(log_dir))
        return reduce_harness(log_dir)

    try:
        with mock.patch.object(trace_reduce, "reduce_dir", both):
            out = drive.run(args.workload, args.seed, args.seconds, True,
                            time.perf_counter())
    except drive.NoDevice as e:
        print(f"program_trace: {e}", file=sys.stderr)
        return 3
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "correct": out["correct"], "metrics": out["metrics"],
                      "device": out["device"], **section,
                      "figures": figures(section)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
