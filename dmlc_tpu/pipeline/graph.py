"""Declarative dataset-pipeline graph → compiled, probed, tunable runs.

``Pipeline`` is the tf.data-style composition layer over the existing
machinery: chaining builds an immutable stage-spec tuple
(``dmlc_tpu.pipeline.stages``), ``build()`` lowers it onto
InputSplit / Parser / ThreadedIter / DiskRowIter / ShardedRowBlockIter —
nothing is reimplemented. Every stage boundary carries a
:class:`~dmlc_tpu.pipeline.stats.StageProbe` (wait time, rows/bytes,
queue occupancy) and every ``"auto"`` depth becomes an
:class:`~dmlc_tpu.pipeline.autotune.Knob` the between-epoch
:class:`~dmlc_tpu.pipeline.autotune.Autotuner` adjusts.

    pipe = (Pipeline.from_uri("train.libsvm", part_index=0, num_parts=1)
            .parse(format="libsvm")
            .batch(16384)
            .prefetch(depth="auto")
            .to_device(window="auto"))
    built = pipe.build(autotune=True)
    for epoch in range(epochs):
        for device_batch in built:          # one epoch
            step(device_batch)
        print(built.stats())                # per-stage snapshot
    built.close()

Ownership contract (the RowBlock lifetime rules, composed once here so
every stage agrees): a stage yields items valid until the consumer's
next pull. Buffering stages (``prefetch``) take ownership of ephemeral
native-engine blocks by detaching their arena lease (falling back to a
copy); ``to_device`` holds the lease until the async transfer lands.
"""

from __future__ import annotations

import time
from collections import deque
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

import numpy as np

from dmlc_tpu.obs import trace as _trace
from dmlc_tpu.obs import watchdog as _watchdog
from dmlc_tpu.obs.metrics import REGISTRY as _METRICS
from dmlc_tpu.pipeline.autotune import Autotuner, Knob
from dmlc_tpu.pipeline.stages import StageSpec, validate_chain
from dmlc_tpu.pipeline.stats import StageProbe, _item_stats, snapshot
from dmlc_tpu.utils.logging import DMLCError, check

__all__ = ["Pipeline", "CompiledPipeline"]

_END = object()


def _probed(runner) -> Iterator:
    """Pull a runner's epoch through its probe: every boundary crossing
    records wait time, volume, and (when queue-backed) occupancy.

    Observability contract (tests/test_obs.py pins it): with a trace
    recorder active, every DELIVERED item emits exactly one complete
    span named ``pull/<stage>`` whose duration is the SAME perf_counter
    pair the probe accumulates into ``wait_s`` — so per-stage span
    totals and probe waits agree by construction (the terminal
    end-of-stream wait goes to ``pull/<stage>.end`` to keep the
    span-count == items invariant exact). While a jax.profiler session
    collects, the same pull is a live span on the profiler's timeline
    (its terminal pull tagged ``end=1``, as its name is known only once
    it returns). Each pull also registers with the stall watchdog while
    it blocks."""
    gen = runner.epoch()
    probe = runner.probe
    pull_name = f"pull/{probe.name}"      # loop-invariant: built once,
    end_name = pull_name + ".end"         # not per delivered item
    while True:
        token = _watchdog.begin_wait(pull_name, runner.wait_detail)
        sp = _trace.begin(pull_name, "pipeline")
        item = _END
        try:
            item = next(gen, _END)
        finally:
            # a raising stage must not leave a phantom wait registered
            # — the watchdog would later report a stall that never was
            _watchdog.end_wait(token)
            # a pull that raised ends the stream like the terminal one
            dt = sp.end(end_name, end=1) if item is _END else sp.end()
        if item is _END:
            probe.record_wait_only(dt)
            return
        probe.record(runner.item_stats(item), dt, runner.queue)
        yield item


class _RunnerBase:
    """One lowered stage: re-enterable epochs + probe + optional knobs."""

    kind = "?"
    owned = True          # items survive past the consumer's next pull
    up: Optional["_RunnerBase"] = None

    def __init__(self, name: str):
        self.probe = StageProbe(name, self.kind)

    @property
    def queue(self):
        """Live bounded queue for occupancy sampling, or None."""
        return None

    def wait_detail(self) -> Dict[str, Any]:
        """Watchdog diagnosis sample for a blocked pull at this stage:
        queue state when queue-backed, plus stage extras (replay tier,
        serve stats) the runner recorded so far."""
        out: Dict[str, Any] = {"kind": self.kind,
                               "items": self.probe.items}
        q = self.queue
        if q is not None:
            try:
                out["qsize"] = q.qsize()
                out["capacity"] = q.capacity
            except Exception:  # noqa: BLE001 — diagnostics only
                pass
        if self.probe.extra:
            out["extra"] = dict(self.probe.extra)
        return out

    def epoch(self) -> Iterator:
        raise NotImplementedError

    def item_stats(self, item) -> tuple:
        """(rows, nnz, bytes) of the item this stage just delivered,
        from host values only."""
        return _item_stats(item)

    def detach_last(self):
        """Take ownership of the last yielded item's arena lease
        (native engine); None when items are already owned."""
        return None

    def knobs(self) -> List[Knob]:
        return []

    def finalize_epoch(self) -> None:
        """Stage-specific snapshot extras (engine stats, drain waits)."""

    def close(self) -> None:
        pass


def _finalize_parser(parser, probe) -> None:
    """Stamp a parser's end-of-epoch telemetry into ``probe``: engine
    stats (+ counter track), the native span-ring drain onto the active
    timeline, and bytes_read. Shared by _ParseRunner and the fused
    _NativeAssembleRunner so both stages report the engine the same
    way."""
    stats_fn = getattr(parser, "stats", None)
    if stats_fn is not None:
        try:
            engine = stats_fn()
            probe.extra["engine"] = engine
            # native-engine counters as a trace counter track: the
            # reader/parse busy split rides next to the spans
            _trace.counter("engine", engine, "native")
        except Exception:  # noqa: BLE001 — telemetry must not kill
            pass
    rec = _trace.active()
    drain = getattr(parser, "drain_trace", None)
    if rec is not None and drain is not None:
        # the engine's span ring (chunk read/tokenize/assemble/
        # cache events) joins the Python spans on ONE timeline
        try:
            drain(rec)
        except Exception:  # noqa: BLE001 — telemetry must not kill
            pass
    try:
        probe.extra["bytes_read"] = int(parser.bytes_read())
    except Exception:  # noqa: BLE001
        pass
    # which decode path served the epoch (parquet: pyarrow golden vs
    # the ABI-8 native page decoder) — obs/analyze's decode evidence
    # names it with its measured GB/s, so a config-5-shaped DECODE-
    # bound verdict says WHICH decoder was the wall
    dp = getattr(parser, "decode_path", None)
    if dp:
        probe.extra["decode_path"] = dp


class _ParseRunner(_RunnerBase):
    """source [+ shuffle] + parse → Parser.create (native or python)."""

    kind = "parse"

    def __init__(self, source: StageSpec, shuffle: Optional[StageSpec],
                 parse: StageSpec):
        super().__init__("parse")
        sp = source.params
        p = dict(parse.params)
        fmt = p.pop("format", None)
        depth = p.pop("prefetch_depth", "auto")
        self._auto_depth = depth == "auto"
        kwargs = {k: v for k, v in p.items() if v is not None}
        self._stream_split = None
        stream = sp.get("stream")
        if stream is not None:
            # streaming source (Pipeline.from_stream): an EOF-less
            # windowed split injected under the python engine (the
            # native reader owns its own split, and a growing file has
            # no frozen byte range for it to own)
            from dmlc_tpu.io.streaming_split import StreamingSplit
            kwargs["engine"] = "python"
            split = StreamingSplit(sp["uri"], **stream)
            self._stream_split = split
            kwargs["split_factory"] = lambda: split
        if shuffle is not None:
            # shuffled read order lowers to an injected split under the
            # python engine (the native reader owns its own split):
            # global_seed → the sample-level GlobalShuffleSplit, else
            # the chunk-level InputSplitShuffle
            kwargs["engine"] = "python"
            chunk = kwargs.get("chunk_size", 8 << 20)
            shp = shuffle.params

            if shp.get("global_seed") is not None:
                from dmlc_tpu.shuffle.split import GlobalShuffleSplit

                def factory():
                    from dmlc_tpu.shuffle.exchange import \
                        DEFAULT_WINDOW_BYTES
                    wb = shp.get("window_bytes") or DEFAULT_WINDOW_BYTES
                    return GlobalShuffleSplit(
                        sp["uri"], sp["part_index"], sp["num_parts"],
                        sp["split_type"], seed=shp["global_seed"],
                        window_bytes=wb)
            else:
                from dmlc_tpu.io.input_split_shuffle import \
                    InputSplitShuffle

                def factory():
                    return InputSplitShuffle.create(
                        sp["uri"], sp["part_index"], sp["num_parts"],
                        sp["split_type"],
                        num_shuffle_parts=shp["num_shuffle_parts"],
                        seed=shp["seed"], chunk_size=chunk)

            kwargs["split_factory"] = factory
        if sp["split_type"] != "text":
            # non-default record framing reaches TextParserBase; the
            # native engine (text reader only) declines it and "auto"
            # falls back to the python golden
            kwargs["split_type"] = sp["split_type"]
        from dmlc_tpu.data.parser import Parser
        self._parser = Parser.create(
            sp["uri"], sp["part_index"], sp["num_parts"], format=fmt,
            prefetch_depth=4 if self._auto_depth else int(depth), **kwargs)
        self.owned = not hasattr(self._parser, "detach")
        if self._stream_split is not None:
            # formats whose parser ignores split_factory (parquet's
            # param struct swallows unknown keys) would silently read
            # the frozen file instead of the stream — refuse (the
            # shuffle-injection precedent below)
            if getattr(self._parser, "_split", None) \
                    is not self._stream_split:
                raise DMLCError(
                    f"pipeline: from_stream is not supported by the "
                    f"{fmt or 'default'} parser (it ignores the "
                    "injected split); streaming works with record-"
                    "stream formats (libsvm/csv/libfm)")
        if shuffle is not None:
            # formats whose parser ignores split_factory (parquet's
            # param struct swallows unknown keys) would silently yield
            # UNshuffled data — refuse instead
            from dmlc_tpu.io.input_split_shuffle import InputSplitShuffle
            from dmlc_tpu.shuffle.split import GlobalShuffleSplit
            split = getattr(self._parser, "_split", None)
            wants = (shuffle.params.get("global_seed") is not None
                     or shuffle.params["num_shuffle_parts"] > 1)
            if (wants and not isinstance(
                    split, (InputSplitShuffle, GlobalShuffleSplit))):
                raise DMLCError(
                    f"pipeline: shuffle is not supported by the "
                    f"{fmt or 'default'} parser (it ignores the "
                    "injected split); shuffle works with record-stream "
                    "formats (libsvm/csv/libfm)")

    @property
    def queue(self):
        return getattr(self._parser, "_prefetch", None)

    def epoch(self) -> Iterator:
        p = self._parser
        p.before_first()
        while p.next():
            yield p.value()

    def detach_last(self):
        detach = getattr(self._parser, "detach", None)
        return detach() if detach is not None else None

    def knobs(self) -> List[Knob]:
        ti = self.queue
        if self._auto_depth and ti is not None:
            return [Knob("parse.chunk_prefetch", "parse",
                         lambda: ti.capacity, ti.set_capacity,
                         lo=1, hi=32)]
        return []

    def finalize_epoch(self) -> None:
        _finalize_parser(self._parser, self.probe)
        if self._stream_split is not None:
            # the monotonic watermark rides the stage extras (and the
            # scheduler's /tenants rows read it live mid-epoch)
            self.probe.extra["stream"] = self._stream_split.watermark()

    def close(self) -> None:
        if self._stream_split is not None:
            self._stream_split.stop()
        if hasattr(self._parser, "destroy"):
            self._parser.destroy()


class _CacheRunner(_RunnerBase):
    """parse + cache → replayed epochs with no re-parse, the tier
    picked by budget (the ShardedRowBlockIter steady-replay story at
    the single-stream level): blocks whose raw bytes fit
    ``memory_budget_bytes`` are retained owned in RAM; larger datasets
    build a DiskRowIter binary page cache (parse once at build, replay
    pages every epoch). An explicit ``path`` forces the page tier (the
    pre-r6 contract); with ``path=None`` the page file is derived under
    the spill dir, fingerprint-keyed so a changed source gets a fresh
    cache, with a sidecar meta for sweep_stale_spill."""

    kind = "cache"

    def __init__(self, source: StageSpec, shuffle: Optional[StageSpec],
                 parse: StageSpec, cache: StageSpec):
        super().__init__("cache")
        check(shuffle is None,
              "pipeline: shuffle + cache is not lowerable (the page "
              "cache replays one fixed order); shuffle after cache via "
              "a map stage, or drop the cache")
        from dmlc_tpu.data.row_iter import DiskRowIter
        sp = source.params
        self._source_uri = sp["uri"]
        self._source_parts = sp["num_parts"]
        p = {k: v for k, v in parse.params.items() if v is not None}
        p.pop("prefetch_depth", None)
        fmt = p.pop("format", None)
        if sp["split_type"] != "text":
            p["split_type"] = sp["split_type"]

        def make_parser():
            from dmlc_tpu.data.parser import Parser
            return Parser.create(sp["uri"], sp["part_index"],
                                 sp["num_parts"], format=fmt, **p)

        path = cache.params["path"]
        budget = cache.params.get("memory_budget_bytes")
        if budget is None:  # not `or`: an explicit 0 must force pages
            budget = 1 << 30
        self._blocks: Optional[List] = None
        self._it = None
        self.tier = "pages"
        fingerprint = self._source_fingerprint(sp)
        if path is None:
            if self._try_memory(make_parser, budget):
                self.tier = "memory"
                return
            path = self._derived_page_path(
                sp, fmt, cache.params["rows_per_page"], fingerprint)
        page_budget = cache.params.get("page_budget_bytes")
        if page_budget is not None:
            # the store owning this cache's root gets the byte budget
            # (LRU eviction of cold committed entries down to it)
            from dmlc_tpu.io.pagestore import PageStore
            PageStore.for_path(path)[0].set_budget(page_budget)
        # DiskRowIter stamps the sidecar itself at commit (and a
        # stamped cache whose sources changed is rebuilt, not replayed)
        self._it = DiskRowIter(make_parser, path,
                               rows_per_page=cache.params["rows_per_page"],
                               fingerprint=fingerprint)

    def _try_memory(self, make_parser, budget: int) -> bool:
        """Drain the parser into owned raw blocks within the budget;
        False (with nothing retained) when the dataset is larger — the
        caller then builds the page tier from a fresh parser. A stat
        pre-check skips the doomed drain outright when the source's
        byte share already exceeds the budget (raw CSR is rarely
        smaller than its text — the same reasoning as
        ShardedRowBlockIter._cache_precheck_ok), so a 10 GB source
        does not parse 1 GiB twice."""
        if not self._memory_precheck_ok(budget):
            return False
        parser = make_parser()
        blocks: List = []
        used = 0
        ok = True
        parser.before_first()
        while parser.next():
            blk = parser.value()
            if getattr(blk, "lease", None) is not None:
                blk = blk.copy()  # own past the parser's next()
            used += blk.memory_cost_bytes()
            if used > budget:
                ok = False
                break
            blocks.append(blk)
        if hasattr(parser, "destroy"):
            parser.destroy()
        if ok:
            self._blocks = blocks
        return ok

    def _memory_precheck_ok(self, budget: int) -> bool:
        try:
            from dmlc_tpu.io.input_split import list_split_files
            total = sum(size for _, size in
                        list_split_files(self._source_uri))
            share = total // max(self._source_parts, 1)
            return share <= budget
        except Exception:  # noqa: BLE001 — non-stat-able: try the drain
            return True

    @staticmethod
    def _source_fingerprint(sp):
        """``[[path, size, mtime_ns], ...]`` of the source's backing
        files, stat'ed through the FileSystem seam (remote ``obj://``
        sources stamp too), or None when non-stat-able."""
        try:
            from dmlc_tpu.io.input_split import list_split_files
            from dmlc_tpu.io.pagestore import stat_fingerprint
            return stat_fingerprint(
                p for p, _ in list_split_files(sp["uri"]))
        except Exception:  # noqa: BLE001 — non-stat-able source
            return None

    @staticmethod
    def _derived_page_path(sp, fmt, rows_per_page: int, fingerprint):
        """Page path under the default store root — fingerprint-keyed
        so a changed source derives a fresh cache file (the stamp
        DiskRowIter writes catches in-place mutation of an unchanged
        name too)."""
        import hashlib
        import os as _os

        from dmlc_tpu.io.pagestore import default_store_dir
        key = hashlib.sha256(repr(
            (sp["uri"], sp["part_index"], sp["num_parts"], fmt,
             rows_per_page, fingerprint)).encode()).hexdigest()[:16]
        d = default_store_dir()
        _os.makedirs(d, exist_ok=True)
        return _os.path.join(d, f"cache-{key}.pages")

    @property
    def queue(self):
        return getattr(self._it, "_iter", None)

    def epoch(self) -> Iterator:
        if self._blocks is not None:
            yield from self._blocks
            return
        it = self._it
        it.before_first()
        while it.next():
            yield it.value()

    def finalize_epoch(self) -> None:
        # which replay tier served the epoch — the autotuner must not
        # judge a knob trial across a tier flip, and readers of the
        # stats need to know which regime a number came from
        self.probe.extra["replay_tier"] = self.tier

    def close(self) -> None:
        self._blocks = None
        if self._it is not None:
            self._it._close()


class _ShardRunner(_RunnerBase):
    """source [+ parse opts] + shard → ShardedRowBlockIter global
    batches ([D, ...] jax.Arrays on the mesh's data axis)."""

    kind = "shard"

    def __init__(self, source: StageSpec, parse: Optional[StageSpec],
                 shard: StageSpec):
        super().__init__("shard")
        from dmlc_tpu.parallel.sharded import ShardedRowBlockIter
        sp = source.params
        p = dict(parse.params) if parse is not None else {}
        p.pop("prefetch_depth", None)
        fmt = p.pop("format", None)
        p = {k: v for k, v in p.items() if v is not None}
        if sp["split_type"] != "text":
            p["split_type"] = sp["split_type"]
        shp = dict(shard.params)
        mesh = shp.pop("mesh")
        self._it = ShardedRowBlockIter(sp["uri"], mesh, format=fmt,
                                       **shp, **p)

    def epoch(self) -> Iterator:
        return iter(self._it)

    def item_stats(self, item) -> tuple:
        # the round's host counts, taken before its put: the global
        # batch's own count arrays live on the devices
        rows, nnz = self._it.last_counts
        return rows, nnz, sum(int(v.nbytes) for v in item.values())

    @property
    def queue(self):
        # the live serve ThreadedIter while an epoch runs: occupancy
        # samples land in the probe, which is what lets the autotuner
        # actually drive the shard.prefetch knob (before r6 the shard
        # stage had no queue telemetry, so the knob never moved)
        return getattr(self._it, "_serve_queue", None)

    def knobs(self) -> List[Knob]:
        it = self._it

        def _set(n: int) -> None:
            it.prefetch_depth = n

        return [Knob("shard.prefetch", "shard",
                     lambda: it.prefetch_depth, _set, lo=1, hi=8)]

    def finalize_epoch(self) -> None:
        it = self._it
        tier = getattr(it, "replay_tier", None)
        if tier is not None:
            self.probe.extra["replay_tier"] = tier
        self.probe.extra["replay_epochs"] = getattr(it, "replay_epochs", 0)
        self.probe.extra["page_replay_epochs"] = getattr(
            it, "page_replay_epochs", 0)
        serve = getattr(it, "_serve_stats", None)
        if serve:
            self.probe.extra["serve"] = dict(serve)
        # the same fields the single-stream assemble stage reports
        engine = it.engine_stats()
        if engine is not None:
            self.probe.extra["engine"] = engine
        self.probe.extra["assemble_s"] = round(it.stack_s, 6)

    def close(self) -> None:
        close = getattr(self._it, "close", None)
        if close is not None:
            close()


class _BatchRunner(_RunnerBase):
    """Re-chunk the block stream to fixed row counts (owned output)."""

    kind = "batch"

    def __init__(self, up: _RunnerBase, rows: int, drop_remainder: bool):
        super().__init__("batch")
        check(rows >= 1, "batch(rows) needs rows >= 1")
        self.up = up
        self._rows = rows
        self._drop = drop_remainder

    def epoch(self) -> Iterator:
        from dmlc_tpu.data.rowblock import RowBlockContainer
        pending: Optional[RowBlockContainer] = None
        for block in _probed(self.up):
            if pending is None:
                pending = RowBlockContainer(block.index.dtype)
            start = 0
            while start < block.size:
                take = min(block.size - start, self._rows - pending.size)
                pending.push_block(block.slice(start, start + take))
                start += take
                if pending.size >= self._rows:
                    yield pending.get_block()
                    pending = RowBlockContainer(block.index.dtype)
        if pending is not None and pending.size and not self._drop:
            yield pending.get_block()


class _PadBatchRunner(_RunnerBase):
    """Padded batch assembly, the Python fused golden: re-chunk the
    block stream to ``rows`` rows, then pad each batch to
    (row_bucket, nnz_bucket) device layout in ONE pass
    (data.padding.pad_single). Output dicts own their arrays. This is
    the fallback — and the byte-parity reference — for the native
    ABI-5 path (_NativeAssembleRunner); tests pin the two equal."""

    kind = "assemble"

    def __init__(self, up: _RunnerBase, spec: StageSpec):
        super().__init__("assemble")
        p = spec.params
        self.up = up
        self._rows = p["rows"]
        self._drop = p["drop_remainder"]
        self._row_bucket = p["row_bucket"] or p["rows"]
        self._nnz_bucket = p["nnz_bucket"]
        self._want_qid = p["want_qid"]
        self._want_field = p["want_field"]
        check(self._rows >= 1, "batch(rows) needs rows >= 1")
        check(self._row_bucket >= self._rows,
              "batch(row_bucket) must be >= rows")
        self._assemble_s = 0.0

    def epoch(self) -> Iterator:
        from dmlc_tpu.data.padding import pad_single
        from dmlc_tpu.data.rowblock import RowBlockContainer
        self._assemble_s = 0.0

        def cut(pending):
            t0 = time.perf_counter()
            padded = pad_single(pending.get_block(), self._row_bucket,
                                self._nnz_bucket, self._want_qid,
                                self._want_field)
            self._assemble_s += time.perf_counter() - t0
            return padded

        pending = None
        for block in _probed(self.up):
            if pending is None:
                pending = RowBlockContainer(block.index.dtype)
            start = 0
            while start < block.size:
                take = min(block.size - start, self._rows - pending.size)
                pending.push_block(block.slice(start, start + take))
                start += take
                if pending.size >= self._rows:
                    yield cut(pending)
                    pending = RowBlockContainer(block.index.dtype)
        if pending is not None and pending.size and not self._drop:
            yield cut(pending)

    def finalize_epoch(self) -> None:
        # which rung assembled the epoch's batches — the benchmark's
        # result line and obs.analyze's evidence read this field
        self.probe.extra["assembly_path"] = "python-fused"
        self.probe.extra["assemble_s"] = round(self._assemble_s, 6)


class _NativeAssembleRunner(_RunnerBase):
    """source + parse + batch(pad=True) fused onto the native engine's
    ABI-5 batch assembly: ``dtp_parser_next_padded`` emits bucket-
    padded, device-layout blocks directly from the parse arena — the
    pad+stack memcpy runs in C with the GIL released and Python never
    touches row bytes on this path. Each yielded PaddedBatch is a dict
    of ZERO-COPY views into a leased padded block (valid until the next
    pull — the standard RowBlock lifetime contract; downstream
    prefetch/to_device detach the lease exactly as they do for CSR
    leases). Byte parity with _PadBatchRunner is pinned by
    tests/test_native_assembly.py."""

    kind = "assemble"
    owned = False  # items are leased engine views

    def __init__(self, parse_runner: "_ParseRunner", spec: StageSpec):
        super().__init__("assemble")
        # take over the already-constructed parser (and its close/stats
        # surface); the parse stage folds into this one
        self._parser = parse_runner._parser
        p = spec.params
        self._rows = p["rows"]
        self._drop = p["drop_remainder"]
        self._row_bucket = p["row_bucket"] or p["rows"]
        self._nnz_bucket = p["nnz_bucket"]
        self._want_qid = p["want_qid"]
        self._want_field = p["want_field"]
        check(self._row_bucket >= self._rows,
              "batch(row_bucket) must be >= rows")

    def epoch(self) -> Iterator:
        p = self._parser
        p.before_first()
        while True:
            batch = p.next_padded(self._rows, self._row_bucket,
                                  self._nnz_bucket, self._want_qid,
                                  self._want_field)
            if batch is None:
                return
            if self._drop and int(batch["num_rows"]) < self._rows:
                continue  # short tail at end of stream
            yield batch

    def detach_last(self):
        return self._parser.detach()

    def finalize_epoch(self) -> None:
        _finalize_parser(self._parser, self.probe)
        self.probe.extra["assembly_path"] = "native-padded"
        eng = self.probe.extra.get("engine") or {}
        if eng.get("assemble_ns") is not None:
            # consumer-side pad+stack memcpy time, measured in the
            # engine (queue waits excluded) — comparable to the python
            # path's assemble_s
            self.probe.extra["assemble_s"] = round(
                eng["assemble_ns"] / 1e9, 6)

    def close(self) -> None:
        if hasattr(self._parser, "destroy"):
            self._parser.destroy()


class _MapRunner(_RunnerBase):
    """User fn over each item. The fn sees the upstream item under the
    upstream's lifetime contract; ownership passes through unchanged."""

    kind = "map"

    def __init__(self, up: _RunnerBase, fn: Callable, name: str):
        super().__init__(name)
        self.up = up
        self._fn = fn
        self.owned = up.owned  # lifetime contract passes through

    def epoch(self) -> Iterator:
        fn = self._fn
        for item in _probed(self.up):
            yield fn(item)

    def detach_last(self):
        return self.up.detach_last()


class _PrefetchRunner(_RunnerBase):
    """Bounded background queue (ThreadedIter). Converts ephemeral
    upstream items to owned ones: the producer thread detaches each
    native arena lease (or copies), and the consumer releases a lease
    when the NEXT item is pulled — preserving the valid-until-next-pull
    contract downstream."""

    kind = "prefetch"

    def __init__(self, up: _RunnerBase, depth):
        super().__init__("prefetch")
        self.up = up
        self._auto = depth == "auto"
        from dmlc_tpu.data.threaded_iter import ThreadedIter
        self._ti = ThreadedIter(
            max_capacity=4 if self._auto else int(depth),
            name="prefetch")
        self._src: Optional[Iterator] = None
        self._started = False

    @property
    def queue(self):
        return self._ti

    def _restart(self) -> None:
        gen = _probed(self.up)
        if self.up.owned:
            self._src = gen
            return

        def owning():
            for item in gen:
                lease = self.up.detach_last()
                if lease is not None:
                    item.lease = lease
                else:
                    item = item.copy()
                yield item

        self._src = owning()

    def epoch(self) -> Iterator:
        if not self._started:
            self._restart()
            self._ti.init(lambda: next(self._src, None), self._restart)
            self._started = True
        else:
            self._ti.before_first()
        prev = None

        def release_prev():
            if prev is not None and getattr(prev, "lease", None) is not None:
                prev.lease.release()
                prev.lease = None

        try:
            while True:
                item = self._ti.next()
                release_prev()
                if item is None:
                    return
                prev = item  # before the yield: an abandoned epoch's
                yield item   # finally must release the CURRENT item too
        finally:
            release_prev()  # the epoch's last lease (or an abandon)

    def knobs(self) -> List[Knob]:
        if not self._auto:
            return []
        return [Knob("prefetch.depth", "prefetch",
                     lambda: self._ti.capacity, self._ti.set_capacity,
                     lo=1, hi=64)]

    def finalize_epoch(self) -> None:
        if self._started:
            # epoch-scoped producer counters (reset on before_first):
            # blocked-on-full-queue time tells consumer-bound from
            # producer-bound without inferring it from occupancy alone
            self.probe.extra["producer"] = self._ti.stats()

    def close(self) -> None:
        self._ti.destroy()


class _DeviceRunner(_RunnerBase):
    """Async host→device transfers with a bounded in-flight window:
    device_put is enqueued immediately, the arena lease (native
    engine) is held until that transfer is drained, and ``window``
    transfers ride under the upstream's work."""

    kind = "to_device"

    def __init__(self, up: _RunnerBase, device, sharding, window,
                 staging="auto"):
        super().__init__("to_device")
        self.up = up
        self._auto = window == "auto"
        self.window = 4 if self._auto else int(window)
        check(self.window >= 1, "to_device(window) needs window >= 1")
        check(device is None or sharding is None,
              "to_device: pass device OR sharding, not both")
        self._target = sharding if sharding is not None else device
        # staging: route batches through a reusable host staging pair
        # (parallel.device_iter.HostStaging) so the source buffers are
        # free at COPY time and the H2D transfer of batch N overlaps
        # batch N+1's assembly. "auto" = on for dict batches (the
        # fixed-shape padded steady path, where slot reuse pays), off
        # for RowBlock streams (variable shapes defeat the pool).
        check(staging in (True, False, "auto"),
              "to_device(staging) must be True, False or 'auto'")
        self._staging = staging
        # host (rows, nnz, bytes) of the batch last delivered, counted
        # before its put: the delivered arrays live on the device
        self._delivered = (0, 0, 0)

    @staticmethod
    def _host_arrays(item) -> Dict[str, np.ndarray]:
        if isinstance(item, dict):
            return item
        out = {"offset": item.offset, "label": item.label,
               "index": item.index}
        for k in ("value", "weight", "qid", "field"):
            v = getattr(item, k)
            if v is not None:
                out[k] = v
        return out

    def item_stats(self, item) -> tuple:
        return self._delivered

    def _platform(self) -> str:
        import jax
        t = self._target
        if t is None:
            return jax.default_backend()
        if hasattr(t, "platform"):       # a Device
            return t.platform
        devs = getattr(t, "device_set", None)  # a Sharding
        if devs:
            return next(iter(devs)).platform
        return jax.default_backend()

    def epoch(self) -> Iterator:
        import jax

        from dmlc_tpu.parallel.device_iter import HostStaging
        target = self._target
        put = (jax.device_put if target is None
               else (lambda x: jax.device_put(x, target)))
        cpu_backend = self._platform() == "cpu"
        # staging pool built lazily at the first dict item under "auto":
        # one pool per epoch, window+1 slots (window in flight + one
        # being staged), no reuse on the aliasing CPU backend
        pool: Optional[HostStaging] = None
        if self._staging is True:
            pool = HostStaging(self.window + 1, alias_unsafe=cpu_backend)
        in_flight: deque = deque()
        xfer_wait = 0.0

        def drain_one():
            nonlocal xfer_wait
            fut, lease, slot, t_enq, counts = in_flight.popleft()
            sp = _trace.begin("to_device.drain", "transfer")
            try:
                jax.block_until_ready(fut)
            finally:
                dt = sp.end(in_flight=len(in_flight) + 1)
            xfer_wait += dt
            self.probe.extra["xfer_wait_s"] = round(xfer_wait, 6)
            if sp.rec is not None and slot is not None:
                # the full async window, enqueue → ready: it overlaps
                # the NEXT batch's to_device.stage span — the
                # Perfetto-visible proof the double-buffer works
                # (recorder only: it overlaps other spans)
                sp.rec.complete("device.xfer", t_enq, sp.t0 + dt - t_enq,
                                "transfer")
            if lease is not None:
                lease.release()
            if slot is not None:
                pool.release(slot)
            self._delivered = counts
            return fut

        for item in _probed(self.up):
            if self.up.owned:
                # an OWNED item may still carry a detached arena lease
                # (prefetch over a native parse): take it over so the
                # upstream's release-on-next-pull cannot return the
                # arena while this async transfer is in flight
                lease = getattr(item, "lease", None)
                if lease is not None:
                    item.lease = None
            else:
                lease = self.up.detach_last()
            arrs = self._host_arrays(item)
            counts = _item_stats(arrs)
            if pool is None and self._staging == "auto" \
                    and isinstance(item, dict):
                pool = HostStaging(self.window + 1,
                                   alias_unsafe=cpu_backend)
            slot = None
            if pool is not None:
                # staged path: one copy into the reusable slot frees
                # the source NOW — a leased padded block returns to the
                # engine pool while its bytes are still in flight
                slot = pool.stage(arrs)
                arrs = slot
                if lease is not None:
                    lease.release()
                    lease = None
                self.probe.extra["staging_assemble_s"] = round(
                    pool.assemble_s, 6)
            elif lease is not None and cpu_backend:
                # the CPU-aliasing rule (io/tpu_fs._device_put_safe):
                # CPU-backend device_put may ALIAS host memory, and a
                # leased arena gets recycled after release — copy now
                # and free the arena immediately. Real accelerator
                # transfers copy, keeping the zero-copy fast path.
                arrs = {k: np.array(v, copy=True) for k, v in arrs.items()}
                lease.release()
                lease = None
            sp = _trace.begin("to_device.put", "transfer")
            try:
                fut = put(arrs)
            finally:
                t_enq = sp.t0 + sp.end()
            in_flight.append((fut, lease, slot, t_enq, counts))
            # window is re-read each round: the autotuner adjusts it
            # between epochs (and a mid-epoch change is simply honored)
            while len(in_flight) > self.window:
                yield drain_one()
        while in_flight:
            yield drain_one()

    def knobs(self) -> List[Knob]:
        if not self._auto:
            return []

        def _set(n: int) -> None:
            self.window = n

        return [Knob("device.window", "to_device",
                     lambda: self.window, _set, lo=1, hi=32)]


class CompiledPipeline:
    """Executable form of a Pipeline: iterate for one epoch, read
    ``stats()``, let the bound autotuner retune depths between epochs."""

    def __init__(self, runners: List[_RunnerBase],
                 autotuner: Optional[Autotuner],
                 tenant: Optional[str] = None):
        self._runners = runners
        self.autotuner = autotuner
        # multi-tenant contract (pipeline.scheduler): the tenant this
        # pipeline bills its pulls to, and the queue-capacity knobs
        # the scheduler owns (the autotuner/controller must not move
        # them — one owner per knob)
        self.tenant = tenant
        self.scheduler_owned: tuple = ()
        self._epoch = 0
        self._last: Optional[Dict[str, Any]] = None
        # one-way hand-off flag: a controller that raised on this
        # pipeline never gets it back (see the epoch hook below)
        self._control_failed = False
        # the pipeline's stats() registers as an obs metrics collector:
        # one REGISTRY.snapshot() sees the last epoch's stage stats
        # next to queue/engine/profiler surfaces (docs/observability.md)
        self._metrics_key = _METRICS.register(
            "pipeline", self, CompiledPipeline._last_snapshot)

    def _last_snapshot(self) -> Optional[Dict[str, Any]]:
        return self._last

    # -- iteration

    def __iter__(self) -> Iterator:
        """One epoch. At a COMPLETE epoch the stats snapshot is frozen
        and the autotuner (if bound) takes its between-epoch step; an
        abandoned epoch leaves the previous snapshot in place.

        Two spans bracket it: ``pipeline.epoch_start``, from entering
        the epoch to just before its first item is yielded (runner
        resets, restarts, the fill of a transfer window: the first
        pull nests inside it), and ``pipeline.epoch_end``, the
        finalize, snapshot and tuning hook after the last pull."""
        start = _trace.begin("pipeline.epoch_start", "pipeline")
        first = True
        for r in self._runners:
            r.probe.reset()
        sched = None
        if self.tenant is not None:
            from dmlc_tpu.pipeline import scheduler as _sched
            sched = _sched.active()
        last = self._runners[-1]
        t0 = time.perf_counter()
        gen = _probed(last)
        try:
            while True:
                # multi-tenant discipline: every delivered batch costs one
                # pull credit FIRST (a credit-blocked tenant stops pulling
                # — its bounded queues fill and the throttle propagates up
                # to its readers), then bills its latency + volume to the
                # tenant's accounting. The billed latency is the
                # tenant-EXPERIENCED wait — credit wait included — because
                # that is what a declared latency SLO (obs.slo) judges: a
                # credit-starved tenant is missing its objective even when
                # its pipeline produces instantly
                if sched is not None:
                    tb = time.perf_counter()
                    sched.acquire(self.tenant)
                item = next(gen, _END)
                if first:
                    first = False
                    start.end()
                if item is _END:
                    break
                if sched is not None:
                    rows, _nnz, nbytes = last.item_stats(item)
                    sched.note_batch(self.tenant,
                                     time.perf_counter() - tb,
                                     rows=rows, nbytes=nbytes)
                yield item
        finally:
            if first:  # the first pull raised
                start.end()
            gen.close()  # an abandoned epoch releases its stages now
        wall = time.perf_counter() - t0
        end = _trace.begin("pipeline.epoch_end", "pipeline")
        try:
            self._end_epoch(wall, sched)
        finally:
            end.end()

    def _end_epoch(self, wall: float, sched) -> None:
        for r in self._runners:
            r.finalize_epoch()
        self._epoch += 1
        self._last = snapshot([r.probe for r in self._runners], wall,
                              self._epoch, self.knob_values())
        if self.tenant is not None:
            # the tenant label rides the snapshot (obs/analyze emits
            # per-tenant bound verdicts from it; /tenants rows cite it)
            self._last["tenant"] = self.tenant
        if sched is not None:
            sched.note_epoch(self.tenant, self._last)
        # one mover per process: an installed verdict-driven
        # controller (obs.control) adopts this pipeline's knobs and
        # subsumes the blind hill-climber — the bound verdict picks
        # WHICH family moves; otherwise the bound autotuner takes its
        # between-epoch step as before
        ctl = None
        if not self._control_failed:
            try:
                from dmlc_tpu.obs import control as _control
                ctl = _control.active()
            except Exception:  # noqa: BLE001 — telemetry never kills
                ctl = None
        if ctl is not None:
            if self.autotuner is not None \
                    and self.autotuner.rail.pending is not None:
                # a controller installed MID-RUN takes over from the
                # autotuner: its in-flight trial would never be judged
                # again — discard it (value restored, no freeze) so no
                # knob is stranded at an unjudged trial value
                self.autotuner.rail.discard()
            try:
                ctl.observe_pipeline(self, self._last)
            except Exception as e:  # noqa: BLE001 — a controller bug
                # must not take down the epoch loop, and it must not
                # SILENTLY disable tuning either. The hand-off is
                # ONE-WAY (this pipeline never returns to the
                # controller): alternating movers would let the
                # autotuner arm a trial the controller's epoch never
                # resolves — a knob stranded at an unjudged value
                from dmlc_tpu.obs.log import warn_limited
                warn_limited(
                    "control-observe-failed",
                    f"obs.control: observe_pipeline failed ({e!r}); "
                    "this pipeline falls back to its own autotuner "
                    "permanently",
                    min_interval_s=60)
                self._control_failed = True
                try:
                    # release this pipeline's controller state: an
                    # unresolved pending trial would wedge every
                    # OTHER source into no-ops, and the controller
                    # must stop moving knobs the autotuner now owns
                    ctl.abandon_pipeline(self)
                except Exception:  # noqa: BLE001
                    pass
                ctl = None
        if ctl is None and self.autotuner is not None:
            self.autotuner.after_epoch(self._last)

    def run_epoch(self) -> Dict[str, Any]:
        """Drain one epoch and return its stats snapshot."""
        for _ in self:
            pass
        assert self._last is not None
        return self._last

    # -- telemetry / tuning

    def stats(self) -> Optional[Dict[str, Any]]:
        """Snapshot of the last COMPLETE epoch (None before the first)."""
        return self._last

    def knobs(self) -> List[Knob]:
        return [k for r in self._runners for k in r.knobs()]

    def knob_values(self) -> Dict[str, int]:
        return {k.name: k.get() for k in self.knobs()}

    def autotune_report(self) -> Optional[Dict[str, Any]]:
        return (self.autotuner.report()
                if self.autotuner is not None else None)

    def trace(self, path: str, capacity: int = 1 << 20):
        """Record a Chrome/Perfetto trace of everything run inside the
        block and export it to ``path`` on exit::

            with built.trace("epoch.json"):
                for batch in built:
                    step(batch)

        Every stage pull becomes a ``pull/<stage>`` span, queue waits
        and transfer drains appear on their own threads, and native
        engine counters ride as counter tracks (dmlc_tpu.obs.trace;
        installs the global recorder for the duration)."""
        return _trace.trace_to(path, capacity)

    def stream_stats(self) -> Optional[Dict[str, Any]]:
        """Live watermark of a streaming source (None for finite
        pipelines) — readable MID-epoch, unlike stats()."""
        src = self._runners[0]
        split = getattr(src, "_stream_split", None)
        return split.watermark() if split is not None else None

    @property
    def epochs(self) -> int:
        return self._epoch

    def close(self) -> None:
        if self.tenant is not None:
            try:
                from dmlc_tpu.pipeline import scheduler as _sched
                sched = _sched.active()
                if sched is not None:
                    sched.release(self)
            except Exception:  # noqa: BLE001 — teardown must not fail
                pass
        if self._metrics_key is not None:
            _METRICS.unregister(self._metrics_key)
            self._metrics_key = None
        for r in reversed(self._runners):
            r.close()

    def __enter__(self) -> "CompiledPipeline":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class Pipeline:
    """Immutable declarative stage chain; see the module docstring."""

    __slots__ = ("_stages",)

    def __init__(self, stages: Tuple[StageSpec, ...]):
        self._stages = stages

    # -- construction

    @staticmethod
    def from_uri(uri: str, part_index: int = 0, num_parts: int = 1,
                 split_type: str = "text") -> "Pipeline":
        """Root of every pipeline: one shard of a (multi-file) URI —
        the InputSplit sharding contract."""
        check(0 <= part_index < num_parts,
              f"part_index {part_index} out of range for {num_parts}")
        return Pipeline((StageSpec("source", uri=uri,
                                   part_index=part_index,
                                   num_parts=num_parts,
                                   split_type=split_type),))

    @staticmethod
    def from_stream(uri: str, *, window_records: Optional[int] = None,
                    window_s: Optional[float] = None,
                    poll_interval_s: float = 0.05,
                    idle_timeout_s: Optional[float] = None,
                    chunk_size: int = 8 << 20) -> "Pipeline":
        """Root of a STREAMING pipeline: an EOF-less windowed read of
        one growing text source (:class:`dmlc_tpu.io.streaming_split.
        StreamingSplit`). Appended records accumulate into windows
        closed by ``window_records`` and/or ``window_s``; each window
        feeds the unchanged parse/batch/to_device machinery, with a
        monotonic watermark in the parse stage's ``extra["stream"]``.
        The epoch ends when the split's ``stop()`` is called and the
        committed bytes are drained, or after ``idle_timeout_s`` with
        no growth (None = stream forever). Streaming sources are
        single-part and cannot be shuffled, cached, or sharded (the
        chain validator rejects those stages)."""
        return Pipeline((StageSpec(
            "source", uri=uri, part_index=0, num_parts=1,
            split_type="text",
            stream={"window_records": window_records,
                    "window_s": window_s,
                    "poll_interval_s": poll_interval_s,
                    "idle_timeout_s": idle_timeout_s,
                    "chunk_size": chunk_size}),))

    def _with(self, spec: StageSpec) -> "Pipeline":
        return Pipeline(self._stages + (spec,))

    def parse(self, format: Optional[str] = None, engine: str = "auto",
              chunk_size: int = 8 << 20, nthreads: Optional[int] = None,
              index_dtype=np.uint32, prefetch_depth="auto",
              **kwargs: Any) -> "Pipeline":
        """Bytes → CSR RowBlock stream (Parser.create; format kwargs
        such as label_column pass through). prefetch_depth="auto" makes
        the python engine's chunk-prefetch queue an autotuner knob."""
        return self._with(StageSpec("parse", format=format, engine=engine,
                                    chunk_size=chunk_size,
                                    nthreads=nthreads,
                                    index_dtype=index_dtype,
                                    prefetch_depth=prefetch_depth,
                                    **kwargs))

    def shuffle(self, num_shuffle_parts: int = 4, seed: int = 0,
                global_seed: Optional[int] = None,
                window_bytes: Optional[int] = None) -> "Pipeline":
        """Shuffled read order. Default: chunk-level
        (InputSplitShuffle) — the shard subdivides into
        num_shuffle_parts sub-shards whose order reshuffles each
        epoch, deterministically from ``seed``.

        ``global_seed`` switches to the gang-wide SAMPLE-level shuffle
        (dmlc_tpu.shuffle.GlobalShuffleSplit): a seeded global
        permutation over every record of the dataset, identical at any
        world size, window-bounded to ``window_bytes`` resident bytes
        (default dmlc_tpu.shuffle.DEFAULT_WINDOW_BYTES), with window
        pages exchanged through the peer /pages tier."""
        check(num_shuffle_parts >= 1, "num_shuffle_parts must be >= 1")
        check(window_bytes is None or window_bytes > 0,
              "shuffle: window_bytes must be > 0")
        check(window_bytes is None or global_seed is not None,
              "shuffle: window_bytes applies to the global shuffle — "
              "pass global_seed")
        return self._with(StageSpec("shuffle",
                                    num_shuffle_parts=num_shuffle_parts,
                                    seed=seed, global_seed=global_seed,
                                    window_bytes=window_bytes))

    def cache(self, path: Optional[str] = None,
              rows_per_page: int = 64 << 10,
              memory_budget_bytes: Optional[int] = None,
              page_budget_bytes: Optional[int] = None) -> "Pipeline":
        """Parse once; later epochs replay instead of re-parsing text.
        The tier is picked by budget (default 1 GiB; an explicit 0
        forces pages): raw blocks within ``memory_budget_bytes`` are
        retained in RAM, larger datasets spill to binary row pages
        (DiskRowIter) under the unified page store, fingerprint-keyed
        AND fingerprint-stamped (a changed source rebuilds instead of
        replaying). An explicit ``path`` forces the page tier at that
        location. ``page_budget_bytes`` sets the owning page store's
        byte budget: committed entries LRU-evict down to it (pinned
        live caches are skipped) — the on-disk analogue of
        ``memory_budget_bytes``.

        The memory tier serves the SAME RowBlock objects every epoch —
        RowBlock is immutable by contract, so downstream ``map`` fns
        must not mutate blocks in place (true of every stage, but here
        a violation corrupts all later epochs instead of one)."""
        return self._with(StageSpec("cache", path=path,
                                    rows_per_page=rows_per_page,
                                    memory_budget_bytes=memory_budget_bytes,
                                    page_budget_bytes=page_budget_bytes))

    def batch(self, rows: int, drop_remainder: bool = False,
              pad: bool = False, row_bucket: Optional[int] = None,
              nnz_bucket: Optional[int] = None, want_qid: bool = False,
              want_field: bool = False) -> "Pipeline":
        """Re-chunk the block stream to exactly ``rows`` rows per block
        (last partial block kept unless drop_remainder).

        ``pad=True`` (or passing ``nnz_bucket``) switches the stage to
        PADDED batch assembly: each batch is a fixed-shape,
        device-layout dict padded to (row_bucket, nnz_bucket) — the
        data.padding layout contract (offset/label/weight/index/value
        + num_rows/num_nnz, optional qid/field). ``row_bucket``
        defaults to ``rows``; ``nnz_bucket`` is required (it bounds the
        batch's nnz — a batch that exceeds it raises). When the stage
        sits directly on a native-engine parse, assembly lowers onto
        the engine's ABI-5 ``dtp_parser_next_padded`` (zero-copy leased
        views, Python never touches row bytes); otherwise the Python
        fused golden pads — byte-identical, pinned. The lowering that
        ran is reported as ``assembly_path`` in the stage stats."""
        pad = pad or nnz_bucket is not None
        if pad:
            check(nnz_bucket is not None,
                  "batch(pad=True) needs nnz_bucket (the padded batch's "
                  "fixed nnz capacity)")
            check(row_bucket is None or row_bucket >= rows,
                  "batch(row_bucket) must be >= rows")
        return self._with(StageSpec("batch", rows=rows,
                                    drop_remainder=drop_remainder,
                                    pad=pad, row_bucket=row_bucket,
                                    nnz_bucket=nnz_bucket,
                                    want_qid=want_qid,
                                    want_field=want_field))

    def map(self, fn: Callable, name: Optional[str] = None) -> "Pipeline":
        """Apply ``fn`` to every item. ``fn`` sees items under the
        upstream lifetime contract (copy before retaining ephemeral
        native blocks)."""
        return self._with(StageSpec("map", fn=fn, name=name or "map"))

    def prefetch(self, depth="auto") -> "Pipeline":
        """Decouple producer and consumer with a bounded background
        queue; depth="auto" is an autotuner knob."""
        return self._with(StageSpec("prefetch", depth=depth))

    def shard(self, mesh, axis: str = "data", row_bucket: int = 1 << 14,
              nnz_bucket: int = 1 << 18, **kwargs: Any) -> "Pipeline":
        """Device-granular multi-host ingest: lowers source+parse into
        ShardedRowBlockIter and yields global [D, ...] jax.Array batch
        dicts sharded on the mesh's ``axis``."""
        return self._with(StageSpec("shard", mesh=mesh, axis=axis,
                                    row_bucket=row_bucket,
                                    nnz_bucket=nnz_bucket, **kwargs))

    def to_device(self, device=None, sharding=None,
                  window="auto", staging="auto") -> "Pipeline":
        """Async host→device transfers, ``window`` in flight;
        window="auto" is an autotuner knob. ``staging`` routes batches
        through a reusable host staging pair (copy frees the source
        immediately; transfer N overlaps assembly N+1, proven by
        to_device.stage/device.xfer spans and the device.staging
        gauge): True, False, or "auto" (on for dict batches — the
        fixed-shape padded steady path — off for RowBlock streams)."""
        return self._with(StageSpec("to_device", device=device,
                                    sharding=sharding, window=window,
                                    staging=staging))

    # -- compilation

    def build(self, autotune: bool = False,
              tenant: Optional[str] = None,
              **autotune_opts: Any) -> CompiledPipeline:
        """Validate the chain and lower it onto the existing iterator
        machinery. ``autotune=True`` binds an Autotuner over every
        "auto" depth knob (no-op when the chain has none).

        ``tenant`` admits the compiled pipeline under that tenant of
        the installed :mod:`dmlc_tpu.pipeline.scheduler` (admission
        control applies — past the tenant's budget this RAISES
        AdmissionError or queues, per the tenant's policy). Every
        delivered batch then costs one scheduler pull credit, volume
        and latency bill to the tenant, and the scheduler owns the
        pipeline's queue-capacity knobs (withheld from the autotuner
        here — one owner per knob)."""
        specs = self._stages
        validate_chain(specs)
        kinds = [s.kind for s in specs]
        if "parse" not in kinds and "shard" not in kinds:
            raise DMLCError(
                "pipeline: nothing to run — add .parse(...) or "
                ".shard(mesh)")
        source = specs[0]
        i = 1
        shuffle_spec = None
        parse_spec = None
        if i < len(specs) and specs[i].kind == "shuffle":
            shuffle_spec = specs[i]
            i += 1
        if i < len(specs) and specs[i].kind == "parse":
            parse_spec = specs[i]
            i += 1
        runners: List[_RunnerBase] = []
        if i < len(specs) and specs[i].kind == "cache":
            runners.append(_CacheRunner(source, shuffle_spec, parse_spec,
                                        specs[i]))
            i += 1
        elif i < len(specs) and specs[i].kind == "shard":
            runners.append(_ShardRunner(source, parse_spec, specs[i]))
            i += 1
        else:
            runners.append(_ParseRunner(source, shuffle_spec, parse_spec))
        for spec in specs[i:]:
            up = runners[-1]
            if spec.kind == "batch" and spec.params.get("pad"):
                # padded assembly sitting DIRECTLY on a native-engine
                # parse fuses into the engine's batch assembly (ABI-5
                # single parser, or the ABI-6 gang for a sharded
                # parse — NativeShardedTextParser.next_padded); anything
                # else (python engine, cache/shuffle upstream, map
                # between) pads through the Python fused golden —
                # byte-identical by the pinned contract
                if (len(runners) == 1 and isinstance(up, _ParseRunner)
                        and hasattr(up._parser, "next_padded")):
                    runners[-1] = _NativeAssembleRunner(up, spec)
                else:
                    runners.append(_PadBatchRunner(up, spec))
            elif spec.kind == "batch":
                runners.append(_BatchRunner(up, spec.params["rows"],
                                            spec.params["drop_remainder"]))
            elif spec.kind == "map":
                runners.append(_MapRunner(up, spec.params["fn"],
                                          spec.params["name"]))
            elif spec.kind == "prefetch":
                runners.append(_PrefetchRunner(up, spec.params["depth"]))
            elif spec.kind == "to_device":
                runners.append(_DeviceRunner(
                    up, spec.params["device"], spec.params["sharding"],
                    spec.params["window"],
                    spec.params.get("staging", "auto")))
            else:  # pragma: no cover — validate_chain rejects these
                raise DMLCError(f"pipeline: unexpected stage {spec.kind!r}")
        sched = None
        owned: tuple = ()
        if tenant is not None:
            from dmlc_tpu.pipeline import scheduler as _sched
            sched = _sched.active()
            if sched is None:
                raise DMLCError(
                    "pipeline: build(tenant=...) needs an installed "
                    "scheduler (dmlc_tpu.pipeline.scheduler.install() "
                    f"or {_sched.ENV_SCHED}=1)")
            owned = _sched.MANAGED_KNOBS
        tuner = None
        if autotune:
            knobs = [k for r in runners for k in r.knobs()
                     if k.name not in owned]
            if knobs:
                tuner = Autotuner(knobs, **autotune_opts)
        built = CompiledPipeline(runners, tuner, tenant=tenant)
        built.scheduler_owned = owned
        if sched is not None:
            try:
                sched.admit(tenant, built)
            except Exception:
                built.close()  # free the runners a failed admission
                raise          # would otherwise leak
        return built

    # -- introspection

    @property
    def stages(self) -> Tuple[StageSpec, ...]:
        return self._stages

    def __repr__(self) -> str:
        return "Pipeline(" + " → ".join(map(repr, self._stages)) + ")"
