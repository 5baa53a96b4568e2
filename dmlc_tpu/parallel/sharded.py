"""Multi-host sharded ingest: local shards → globally sharded jax.Array.

The TPU-native analogue of the reference's distributed story (SURVEY.md
§2.4, §5.8): the reference gives each worker a disjoint byte range via
InputSplit(uri, rank, world) and leaves assembly to the learner; here the
dataset is sharded at *device* granularity — global device d parses part
d of num_devices — and each field assembles into ONE global jax.Array of
shape [num_devices, ...] sharded on the mesh's data axis via
jax.make_array_from_process_local_data. Collectives then ride ICI/DCN via
XLA (no sockets, no NCCL translation; the tracker's control-plane job is
jax.distributed — see dmlc_tpu.parallel.launch).

Layout contract (the SPMD-friendly shape for CSR):
every device holds its OWN padded CSR block —
  offset [D, row_bucket+1] int64   (D = global devices, dim 0 sharded)
  label/weight [D, row_bucket] f32
  index [D, nnz_bucket] u32/u64, value [D, nnz_bucket] f32
  num_rows/num_nnz [D] int32       (true sizes under the padding)
Consumers shard_map over the data axis: each device computes on its block
with static shapes, then psum/all_gather as needed (dmlc_tpu.ops).
Padded rows are compute-neutral: weight 0, empty; padded nnz: value 0.
"""

from __future__ import annotations

from typing import Any, Dict, Iterator, List, Optional

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dmlc_tpu.data.padding import (  # noqa: F401 — re-exported: the
    # padding/layout contract moved to data/padding.py (one home shared
    # with the native engine's ABI-5 padded blocks); existing importers
    # keep finding the names here
    ensure_schema, pad_to_bucket, stack_padded_rows,
)
from dmlc_tpu.data.rowblock import RowBlock
from dmlc_tpu.obs import trace as _trace
from dmlc_tpu.utils.logging import (
    DMLCError, check, check_eq,
)

__all__ = ["pad_to_bucket", "stack_device_batches", "make_global_batch",
           "make_replicated", "stack_padded_rows", "ShardedRowBlockIter",
           "next_pow2_bucket", "empty_block", "ensure_schema"]


def next_pow2_bucket(n: int, minimum: int = 8) -> int:
    """Smallest power of two >= max(n, minimum) — bounds compile count."""
    b = minimum
    while b < n:
        b <<= 1
    return b


def empty_block(index_dtype=np.uint32) -> RowBlock:
    """A zero-row block (pads out exhausted shards on skewed data)."""
    return RowBlock(offset=np.zeros(1, np.int64),
                    label=np.zeros(0, np.float32),
                    index=np.zeros(0, index_dtype))


def stack_device_batches(batches: List[Dict[str, np.ndarray]]
                         ) -> Dict[str, np.ndarray]:
    """Per-device padded dicts → one local dict with leading device dim."""
    check(len(batches) > 0, "no device batches")
    keys = batches[0].keys()
    for b in batches[1:]:
        check_eq(set(b.keys()), set(keys), "inconsistent batch keys")
    return {k: np.stack([np.asarray(b[k]) for b in batches]) for k in keys}


def make_global_batch(local: Dict[str, np.ndarray], mesh: Mesh,
                      axis: str = "data") -> Dict[str, jax.Array]:
    """Local stacked batch [local_devices, ...] → global jax.Arrays
    [global_devices, ...] sharded on the mesh's data axis.

    Every process calls this collectively with same-shaped locals; dim 0
    is the device-shard dim (this process's local batches), stitched into
    the global array without any host gather.
    """
    out: Dict[str, jax.Array] = {}
    for k, v in local.items():
        v = np.asarray(v)
        check(v.ndim >= 1, f"{k}: batch arrays need a leading shard dim")
        sharding = NamedSharding(mesh, P(axis, *([None] * (v.ndim - 1))))
        out[k] = jax.make_array_from_process_local_data(sharding, v)
    return out


def make_replicated(tree, mesh: Mesh):
    """Host pytree → fully replicated global jax.Arrays on ``mesh``.

    Built with make_array_from_single_device_arrays (each local device
    gets a copy), NOT ``jax.device_put(x, replicated_sharding)``: for a
    numpy input and a non-fully-addressable sharding, device_put runs a
    cross-process assert_equal collective per leaf — a per-call tax on
    real gangs, and outright unsupported on the multiprocess CPU
    backend. Callers must pass value-identical trees on every process
    (the usual seeded-init contract); nothing verifies it here.
    """
    import jax as _jax
    sharding = NamedSharding(mesh, P())

    def put(x):
        x = np.asarray(x)
        arrs = [_jax.device_put(x, d) for d in mesh.local_devices]
        return _jax.make_array_from_single_device_arrays(
            x.shape, sharding, arrs)

    return _jax.tree_util.tree_map(put, tree)


class ShardedRowBlockIter:
    """Device-granular sharded ingest: global device d reads part d.

    This process parses parts [proc*L, (proc+1)*L) where L = local device
    count, pads each device's block stream to (row_bucket, nnz_bucket),
    stacks, and assembles global arrays. Skewed shards are padded with
    empty blocks until every device's stream is exhausted, so all
    processes always agree on batch count (a collective requirement).

    Reference seam: InputSplit(uri, rank, world) per worker →
    here num_parts = total devices and assembly is a jax.Array.

    Steady-epoch replay (reference: disk_row_iter.h's parse-once/
    replay-epochs, composed in two tiers): epochs after the first serve
    retained rounds of RAW (unpadded) blocks — padded, stacked and
    transferred on the serve-prefetch thread — whenever (a)
    ``steady_replay`` is on (default) and (b) a per-file
    (size, mtime_ns, ctime_ns, inode) fingerprint still matches. The
    tier is picked by budget: rounds whose raw bytes fit
    ``agreement_cache_bytes`` stay in memory; larger rounds SPILL to a
    fingerprint-stamped binary page file (``spill_dir``, DiskRowIter's
    page format generalized to rounds) and steady epochs replay pages
    at disk rate instead of re-parsing text every epoch (the
    larger-than-RAM case, exactly where parse is most expensive).
    ``replay_tier`` reports which tier served the last epoch
    ("parse" | "memory" | "pages"); ``page_replay_epochs`` counts the
    page-served ones. On any fingerprint mismatch the epoch
    transparently re-parses with the replay-count mutation assertions
    (truncation/rewrite raise DMLCError, appended bytes stay invisible)
    and re-earns replay by teeing the clean re-parse. The first epoch
    of a single-process "auto" run streams (fast first batch); its
    epoch 2 re-parses and tees; epochs 3+ replay.

    Spans (obs.trace; on the profiler's timeline while one collects),
    on whichever thread assembles a round: ``shard.parse`` (one block
    from each part's parser), ``shard.stack`` (pad and stack) and
    ``shard.put`` (the global batch). ``last_counts`` holds the host
    (rows, nnz) of the round last yielded, ``stack_s`` the epoch's
    pad-and-stack seconds, and ``engine_stats()`` the parts' engine
    counters.
    """

    def __init__(self, uri: str, mesh: Mesh, format: Optional[str] = None,
                 axis: str = "data", row_bucket: int = 1 << 14,
                 nnz_bucket: int = 1 << 18, index_dtype=np.uint32,
                 agreement_cache_bytes: int = 1 << 30,
                 first_epoch_cache: str = "auto",
                 steady_replay: bool = True, page_spill: bool = True,
                 spill_dir: Optional[str] = None, **parser_kwargs):
        from dmlc_tpu.data.parser import Parser
        check(first_epoch_cache in ("auto", "always", "never"),
              "first_epoch_cache must be auto|always|never")
        self.mesh = mesh
        self.axis = axis
        self.row_bucket = row_bucket
        self.nnz_bucket = nnz_bucket
        self.index_dtype = np.dtype(index_dtype)
        # rounds-per-epoch, agreed collectively during the FIRST epoch and
        # cached: replay is deterministic (same uri/parts/buckets), so
        # later epochs run with ZERO per-batch collectives — matching the
        # reference, whose distributed story (input_split_base.cc) has no
        # cross-worker communication at all once shards are assigned.
        # Epoch 1 itself agrees with ONE allgather (of per-process round
        # counts) when the local shard fits in agreement_cache_bytes of
        # cached blocks; only the over-budget fallback pays the legacy
        # per-round done-flag collective (VERDICT r3 #6).
        self.agreement_cache_bytes = agreement_cache_bytes
        # "auto": cache only when there IS a collective to save
        # (process_count > 1) — single-process jobs keep streaming
        # epoch 1 (first batch after one block parse, no cache RSS).
        # "always"/"never" force either path (tests, tuning).
        self.first_epoch_cache = first_epoch_cache
        # Steady-epoch replay (VERDICT r4 #2, page tier r6): keep the
        # epoch-1 rounds as RAW (unpadded) block rows and serve later
        # epochs from them — padded/stacked/transferred on the serve-
        # prefetch thread — instead of re-parsing the text. Rounds
        # within agreement_cache_bytes of RAW bytes stay in memory
        # (steady RSS ~ raw text size, not the several-x padded size
        # the r5 tee retained); larger rounds spill to a binary page
        # file and replay at page rate (1.4-2.0 GB/s text-equivalent
        # vs the 0.22 GB/s parse path, measured on the r4 CPU box).
        # Guarded by a per-file fingerprint captured before the cached
        # parse: ANY mismatch falls back to the legacy re-parse epoch,
        # whose count assertions implement the exact mutation semantics
        # (truncation/rewrite raise, appends stay invisible) — replay
        # is a pure optimization, never a semantics change. Retained
        # blocks are written once and only read afterwards, so CPU-
        # backend device_put aliasing (io/tpu_fs._device_put_safe)
        # cannot corrupt served batches (serve-time padding copies into
        # fresh arrays every round anyway).
        self.steady_replay = steady_replay
        self.page_spill = page_spill
        self._spill_dir = spill_dir
        self.replay_epochs = 0        # replay-served epochs (all tiers)
        self.page_replay_epochs = 0   # ... of which from the page tier
        self.replay_tier: Optional[str] = None  # last epoch's server
        self._round_store: Optional["ShardedRowBlockIter._RoundStore"] \
            = None
        self._fingerprint = None
        self._was_pages = False  # last dropped store was page-tier:
        # its re-earn tee starts spilled (the shard is known over
        # budget; memory accumulation would be redundant copying)
        self._serve_queue = None  # live serve ThreadedIter (probes)
        self._serve_stats: Optional[Dict[str, float]] = None
        self.last_counts = (0, 0)
        self.stack_s = 0.0
        # serve-side prefetch lookahead (rounds assembled ahead of the
        # consumer); dmlc_tpu.pipeline exposes it as an autotuner knob
        self.prefetch_depth = 2
        # optional-key schema (qid/field), observed locally and OR-agreed
        # across processes so every rank pads exhausted parts to the SAME
        # key set (ADVICE r4)
        self._has_qid = False
        self._has_field = False
        # ADVICE r5: a qid/field column that first appears MID-file flips
        # the batch key set at the discovery round — consumers then see
        # jit recompiles / key mismatches with no signal. Warn ONCE, the
        # moment the flip happens after round 0.
        self._schema_rounds = 0
        self._schema_warned = False
        self._rounds_per_epoch: Optional[int] = None
        # per-part block counts from epoch 1: later epochs assert the
        # replay produced exactly these (file-mutation detector)
        self._part_rounds: Optional[List[int]] = None
        axis_idx = list(mesh.axis_names).index(axis)
        total_parts = mesh.devices.shape[axis_idx]
        local = [d for d in mesh.local_devices]
        # which data-axis coordinates live on this process
        mesh_devs = mesh.devices.reshape(mesh.devices.shape)
        coords = []
        for c, dev in np.ndenumerate(mesh_devs):
            if dev.process_index == jax.process_index():
                coords.append(c[axis_idx])
        self._my_parts = sorted(set(coords))
        check(len(self._my_parts) > 0, "process owns no mesh devices")
        self._uri = uri
        self._total_parts = total_parts
        self._parsers = [
            Parser.create(uri, p, total_parts, format=format,
                          index_dtype=index_dtype, **parser_kwargs)
            for p in self._my_parts]
        # (path, size) at construction: steady epochs stat-check these
        # BEFORE touching any reader — a shrunk file under the native
        # engine's mmap views is SIGBUS (uncatchable), so the shrink
        # must be detected by stat, not by reading
        try:
            from dmlc_tpu.io.input_split import list_split_files
            self._ctor_sizes = list_split_files(uri)
        except Exception:  # noqa: BLE001 — non-stat-able backing
            self._ctor_sizes = None
        # per-iterator obs collector (weakly held): replay tier +
        # epoch counters land in one metrics snapshot per LIVE
        # iterator, next to the queue/engine surfaces
        from dmlc_tpu.obs.metrics import REGISTRY as _registry
        import os as _os
        self._obs_key = _registry.register(
            f"shard/{_os.path.basename(uri.split('?', 1)[0])}",
            self, ShardedRowBlockIter._metrics)

    def _first_epoch_batches(self) -> Iterator[tuple]:
        """Epoch 1: agree on rounds-per-epoch across processes.

        Fast path (one collective): parse AND pad the local parts into
        an in-memory cache, allgather the per-process round counts ONCE,
        then assemble global batches from the cache padding exhausted
        parts. Falls back to the legacy per-round done-flag agreement
        when the local shard exceeds ``agreement_cache_bytes`` (a
        larger-than-budget epoch 1 then pays one tiny collective per
        round — later epochs are always collective-free either way).
        """
        want_cache = (self.first_epoch_cache == "always" or
                      (self.first_epoch_cache == "auto" and
                       jax.process_count() > 1))
        # fingerprint BEFORE the caching parse reads any byte: a file
        # mutated DURING the pass then mismatches at the next epoch's
        # replay check and the stale rounds are dropped
        fp = self._fingerprint_now() if want_cache else None
        cached = self._try_cache_epoch() if want_cache else None
        local_rounds = (max((len(c) for c in cached), default=0)
                        if cached is not None else -1)
        # ONE allgather carries the protocol vote, the round count, AND
        # the optional-key schema: whether a process cached is a LOCAL
        # fact (shard size vs budget), and mixing protocols across
        # processes would mismatch collectives — so the fast path runs
        # only if EVERY process cached, decided by the same collective
        # that agrees the rounds
        all_cached, rounds = self._agree_first_epoch(
            cached is not None, local_rounds)
        if all_cached:
            assert cached is not None
            self._part_rounds = [len(c) for c in cached]
            self._rounds_per_epoch = rounds
            empty = empty_block(self.index_dtype)
            # the cache pass enforced the raw-byte budget, so this tee
            # lands in the memory tier (it takes ownership of the
            # cached blocks — no second copy); only the shared empty
            # pads nudge its accounting past the cache pass's
            tee = self._make_tee(fp, owned_rows=True)

            def assemble_round(r: int) -> Dict[str, jax.Array]:
                row = [c[r] if r < len(c) else empty for c in cached]
                # pad/stack at serve time (this runs on the prefetch
                # producer thread): the counting pass stays pure parse
                # and the retained rounds stay RAW
                stacked = self._assemble_stacked(row)
                for c in cached:
                    if r < len(c):
                        c[r] = None  # the tee owns the blocks now
                tee.add_row(row)
                return self._put(stacked)

            # pad+stack+assembly for round r+1 runs on a background
            # thread while the consumer works on round r: claws back
            # the parse/consume overlap that cache-then-replay
            # serializes (steady epochs get it for free from streaming)
            rr = iter(range(rounds))
            try:
                yield from self._prefetch_serve(
                    lambda: (assemble_round(r)
                             if (r := next(rr, None)) is not None
                             else None))
                # commit the replay rounds only on a COMPLETE
                # un-abandoned epoch whose files re-stat unchanged
                tee.commit(self, rounds)
            finally:
                tee.close()
            return
        # some process exceeded its budget: EVERYONE runs the legacy
        # per-round agreement (skewed shards make a process exhaust
        # early; it must keep yielding empty batches until ALL are done
        # — batch count is a collective contract), counting rounds so
        # every later epoch skips the collective entirely. A local cache
        # is dropped rather than used for assembly so both sides of the
        # protocol stay identical — but the epoch is still TEED locally
        # when this process wanted to cache: the tee is not part of the
        # protocol (replay and re-parse produce the same global-batch
        # call sequence), and an over-budget shard spills its rounds to
        # pages here, earning page replay from epoch 2 on.
        # force_spill when THIS rank's cache pass just measured the
        # shard over budget (cached is None despite wanting to cache):
        # re-accumulating up to the budget in memory a second time only
        # to flush it to the writer would be pure redundant copying. A
        # rank that cached fine but lost the vote keeps the memory tier.
        over_budget = want_cache and cached is None
        cached = None
        tee = (self._make_tee(fp, force_spill=over_budget) if want_cache
               else self._ReplayTee(0, None, None))
        its, done, counts = self._restart_streams()
        rounds = 0
        try:
            while True:
                row = self._next_row(its, done, counts)
                if self._all_processes_done(all(done)):
                    self._part_rounds = counts
                    self._rounds_per_epoch = rounds
                    tee.commit(self, rounds)
                    return
                rounds += 1
                tee.add_row(row)
                yield self._assemble(row)
        finally:
            tee.close()

    def _replay_store(self, store: "ShardedRowBlockIter._RoundStore"
                      ) -> Iterator[tuple]:
        """Serve an epoch from retained raw rounds (memory or pages):
        zero parsing — the serve-prefetch thread pads, stacks and
        enqueues transfers one round ahead of the consumer. One
        producer on purpose: page decode and pad/stack are BOTH
        memcpy-bound, so a second serve thread just thrashes small-core
        hosts (measured −35% here); the page read already overlaps the
        consumer's step through _prefetch_serve. No collectives (the
        replay path and the re-parse path produce the same global-batch
        call sequence, so ranks may mix paths when only SOME see a
        local mutation — or sit in different tiers)."""
        rows = store.iter_rows()

        def make():
            row = next(rows, None)
            if row is None:
                return None
            return self._assemble(row)

        try:
            yield from self._prefetch_serve(make)
        finally:
            if hasattr(rows, "close"):
                rows.close()

    def _fingerprint_now(self):
        """(path, size, mtime_ns, ctime_ns, inode) per backing file, or
        None when the scheme has no stat (then replay never engages —
        no regression, the re-parse path simply keeps running every
        epoch). Inode catches replace-by-rename (the common safe-write
        pattern keeps size and may land in the same coarse timestamp
        tick); ctime catches in-place rewrites whose mtime was then
        backdated. Residual blind spot: an in-place same-size rewrite
        within the SAME nanosecond tick as the fingerprinted stat —
        accepted (the re-parse path it replaced could also miss a
        same-size same-row-count rewrite)."""
        from dmlc_tpu.io.input_split import list_split_files
        from dmlc_tpu.io.pagestore import stat_uri
        try:
            out = []
            for path, _size in list_split_files(self._uri):
                size, mtime_ns, ctime_ns, ino = stat_uri(path)
                out.append((path, size, mtime_ns, ctime_ns, ino))
            return tuple(out)
        except Exception:  # noqa: BLE001 — any non-stat-able backing
            return None

    class _RoundStore:
        """Retained epoch-1 rounds, served on steady epochs. Rows are
        RAW (unpadded) per-part blocks; padding happens at serve time
        on the prefetch thread."""

        tier = "?"

        def iter_rows(self) -> Iterator[List[RowBlock]]:
            raise NotImplementedError

        def drop(self) -> None:
            pass

    class _MemoryRounds(_RoundStore):
        tier = "memory"

        def __init__(self, rows: List[List[RowBlock]], nbytes: int):
            self.rows: Optional[List[List[RowBlock]]] = rows
            self.nbytes = nbytes  # raw block bytes (soak tests pin RSS)

        def iter_rows(self):
            return iter(self.rows or [])

        def drop(self):
            self.rows = None

    class _PageRounds(_RoundStore):
        tier = "pages"

        def __init__(self, spill_file):
            self.file = spill_file  # dmlc_tpu.data.row_iter.RoundSpillFile

        def iter_rows(self):
            return self.file.iter_rows()

        def drop(self):
            self.file.delete()

    class _ReplayTee:
        """Accumulate raw rounds within the byte budget, SPILLING to a
        binary page file when they exceed it; commit only a COMPLETE
        epoch whose backing files re-stat to the fingerprint captured
        before the epoch's parse began (a file mutated DURING the pass
        must not arm replay with half-old half-new rounds). Shared by
        the epoch-1 fast path, the epoch-1 legacy path, and the
        re-parse tee so the budget/spill/commit invariant lives in one
        place. ``owned_rows`` marks rows whose blocks the caller hands
        over (the epoch-1 cache pass); otherwise blocks may be
        ephemeral arena views and the memory tier copies them (the
        spill writer serializes immediately, so it never copies).
        ``start_spilled`` skips the doomed memory accumulation when a
        size pre-check already proved the shard over budget."""

        def __init__(self, budget: int, fp, spill_path: Optional[str],
                     owned_rows: bool = False,
                     start_spilled: bool = False):
            self.budget = budget
            self.fp = fp
            self.active = fp is not None and budget > 0
            self.spill_path = spill_path
            self.owned_rows = owned_rows
            self.rows: List[List[RowBlock]] = []
            self.used = 0
            self._writer = None
            self._committed = False
            # opened lazily at the first row (its width = nparts)
            self._spill_on_first_row = start_spilled
            if self.active and start_spilled and spill_path is None:
                self.active = False

        def _writer_for(self, nparts: int):
            from dmlc_tpu.data.row_iter import RoundSpillWriter
            meta = {"fingerprint": [list(e) for e in self.fp]
                    if self.fp else None}
            return RoundSpillWriter(self.spill_path, nparts, meta)

        def add_row(self, blocks: List[RowBlock]) -> None:
            if not self.active:
                return
            try:
                self._add_row(blocks)
            except Exception as e:  # noqa: BLE001 — a full/unwritable
                # disk must degrade to "no replay", never kill the epoch
                from dmlc_tpu.obs.log import warn_limited
                warn_limited(
                    "sharded-spill-failed",
                    f"ShardedRowBlockIter: replay spill failed "
                    f"({e}); steady epochs will re-parse",
                    min_interval_s=60.0, all_ranks=True)
                self._abandon()

        def _add_row(self, blocks: List[RowBlock]) -> None:
            if self._writer is None and self._spill_on_first_row:
                self._writer = self._writer_for(len(blocks))
                self._spill_on_first_row = False
            if self._writer is not None:
                self._writer.add_row(blocks)
                return
            row = (list(blocks) if self.owned_rows
                   else [b.copy() for b in blocks])
            self.used += sum(b.memory_cost_bytes() for b in row)
            if self.used <= self.budget:
                self.rows.append(row)
                return
            # over budget: move to the page tier (or abandon when
            # spilling is off — the pre-r6 behavior)
            if self.spill_path is None:
                self._abandon()
                return
            self._writer = self._writer_for(len(blocks))
            for r in self.rows:
                self._writer.add_row(r)
            self._writer.add_row(row)
            self.rows = []

        def _abandon(self) -> None:
            self.active = False
            self.rows = []
            if self._writer is not None:
                self._writer.abort()
                self._writer = None

        def commit(self, it: "ShardedRowBlockIter",
                   expected_rounds: int) -> None:
            if not self.active:
                return
            got = (self._writer.rounds if self._writer is not None
                   else len(self.rows))
            if got != expected_rounds or it._fingerprint_now() != self.fp:
                self._abandon()
                return
            if self._writer is not None:
                try:
                    spill_file = self._writer.commit()
                except Exception as e:  # noqa: BLE001 — same degrade-
                    # to-no-replay contract as add_row: a commit-time
                    # ENOSPC/unlink must not kill a COMPLETE epoch
                    from dmlc_tpu.obs.log import warn_limited
                    warn_limited(
                        "sharded-spill-commit-failed",
                        f"ShardedRowBlockIter: replay spill commit "
                        f"failed ({e}); steady epochs will re-parse",
                        min_interval_s=60.0, all_ranks=True)
                    self._abandon()
                    return
                it._round_store = ShardedRowBlockIter._PageRounds(
                    spill_file)
                self._writer = None
            else:
                it._round_store = ShardedRowBlockIter._MemoryRounds(
                    self.rows, self.used)
                self.rows = []
            it._fingerprint = self.fp
            self._committed = True

        def close(self) -> None:
            """Abort an un-committed spill (abandoned epoch): the .tmp
            must not linger as if it were a cache."""
            if not self._committed:
                self._abandon()

    def _make_tee(self, fp, owned_rows: bool = False,
                  force_spill: bool = False) -> "_ReplayTee":
        """A two-tier tee for this iterator: memory within the budget,
        page spill above it (when enabled), starting directly in spill
        mode when the size pre-check — or the caller's stronger
        evidence (``force_spill``: a cache pass that just measured the
        shard over budget) — proves memory accumulation doomed."""
        if not self.steady_replay:
            return self._ReplayTee(0, None, None)
        return self._ReplayTee(
            self.agreement_cache_bytes, fp, self._spill_path(),
            owned_rows=owned_rows,
            start_spilled=(self.page_spill
                           and (force_spill
                                or not self._cache_precheck_ok())))

    # itertools.count: next() is atomic in CPython, so concurrent tees
    # from different threads can never derive the same spill path (a
    # bare `seq[0] += 1` could, and two writers would then interleave
    # into one .tmp)
    import itertools as _itertools
    _SPILL_SEQ = _itertools.count(1)

    def _spill_path(self) -> Optional[str]:
        """Unique per-instance spill file under spill_dir, keyed by the
        shard identity (uri/parts/buckets) so the name is self-
        describing; the fingerprint rides in the file header for
        sweep_stale_spill. None disables the page tier."""
        if not self.page_spill:
            return None
        import hashlib
        from dmlc_tpu.data.row_iter import default_spill_dir
        key = hashlib.sha256(repr(
            (self._uri, self._total_parts, self._my_parts,
             self.row_bucket, self.nnz_bucket,
             str(self.index_dtype))).encode()).hexdigest()[:16]
        import os
        return os.path.join(
            self._spill_dir or default_spill_dir(),
            f"rounds-{key}-p{os.getpid()}-{next(self._SPILL_SEQ)}.pages")

    def _prefetch_serve(self, make_next) -> Iterator[tuple]:
        """Serve batches from a background producer, one round ahead:
        assembly/transfer of round r+1 overlaps the consumer's work on
        round r. The live queue is exposed as ``_serve_queue`` while an
        epoch runs (pipeline probes sample its occupancy — that is what
        lets the autotuner drive the shard.prefetch knob) and its
        producer stats land in ``_serve_stats`` at epoch end."""
        from dmlc_tpu.data.threaded_iter import ThreadedIter
        ti = ThreadedIter(max_capacity=self.prefetch_depth,
                          name="shard.serve")
        ti.init(make_next)
        self._serve_queue = ti
        try:
            while (batch := ti.next()) is not None:
                yield batch
        finally:
            self._serve_queue = None
            self._serve_stats = ti.stats()
            ti.destroy()

    def _steady_stream(self) -> Iterator[List[RowBlock]]:
        """Epochs 2+: replay the agreed round count with ZERO
        collectives, then assert the replay matched epoch 1 — if the
        underlying file changed between epochs (the mmap-truncation
        class of hazard), streams would silently yield short or long and
        desynchronize the collective batch contract; turn that into a
        loud error instead (VERDICT r3 #7)."""
        part_rounds = self._part_rounds
        assert part_rounds is not None  # set with _rounds_per_epoch
        its, done, counts = self._restart_streams()
        for _ in range(self._rounds_per_epoch):
            try:
                row = self._next_row(its, done, counts)
            except DMLCError as e:
                raise self._mutation_error(cause=e) from e
            # fail FAST on a shrunk part: a stream that exhausted short
            # of its epoch-1 count is conclusive evidence the moment it
            # happens — raising here keeps the consumer from training
            # the rest of the epoch on empty-padded garbage before a
            # post-loop check could notice
            for i in range(len(its)):
                if done[i] and counts[i] < part_rounds[i]:
                    raise self._mutation_error(
                        part=self._my_parts[i], got=counts[i],
                        want=part_rounds[i])
            yield row
        for i, it in enumerate(its):
            grew = False
            if not done[i]:
                try:
                    next(it)
                    grew = True
                except StopIteration:
                    pass
                except DMLCError as e:
                    # the probe read bytes past the last replayed block
                    # that failed to parse: same hazard, same context
                    raise self._mutation_error(cause=e) from e
            if grew or counts[i] != part_rounds[i]:
                raise self._mutation_error(
                    part=self._my_parts[i], got=counts[i],
                    want=part_rounds[i], grew=grew)

    @staticmethod
    def _mutation_error(part=None, got=None, want=None, grew=False,
                        cause=None) -> DMLCError:
        detail = (f"part {part} replayed {got} blocks"
                  f"{' and kept going' if grew else ''} where epoch 1 "
                  f"produced {want}"
                  if cause is None
                  else f"error replaying data that parsed cleanly in "
                       f"epoch 1: {cause}")
        return DMLCError(
            f"ShardedRowBlockIter: {detail} — the underlying file "
            "changed between epochs of one iterator (deterministic "
            "replay is the contract; recreate the iterator after "
            "mutating inputs)")

    def _note_schema(self, has_qid: bool, has_field: bool) -> None:
        """OR newly observed optional keys into the schema, warning ONCE
        if a key first appears after the first assembled round (ADVICE
        r5): from that round on the per-batch key set differs from the
        earlier rounds' (and from replay/re-parse epochs, which carry
        the keys from round 0) — consumers see jit recompiles or key
        mismatches. The fix is uniform columns: tag every row (qid) /
        every feature (field), or none."""
        if self._schema_rounds > 0 and not self._schema_warned:
            flipped = [name for name, seen, new in (
                ("qid", self._has_qid, has_qid),
                ("field", self._has_field, has_field)) if new and not seen]
            if flipped:
                self._schema_warned = True
                # obs.log channel, rank 0 only in a gang — every rank
                # detects the same flip, N copies say nothing new.
                # min_interval_s=0: the per-instance flag above owns
                # the once-semantics (an id(self)-keyed dedup could
                # silently eat a DIFFERENT iterator's warning after
                # CPython reuses the address)
                from dmlc_tpu.obs.log import warn_limited
                warn_limited(
                    "sharded-schema-flip",
                    f"ShardedRowBlockIter: optional column(s) "
                    f"{'/'.join(flipped)} first appeared after "
                    f"{self._schema_rounds} assembled round(s) — the "
                    "batch key set changes at this round and will differ "
                    "from earlier rounds and from replay/re-parse epochs "
                    "(expect jit recompiles / pytree-structure "
                    "mismatches). Supply uniform columns: tag every row "
                    "(qid) / every feature (field), or none.",
                    min_interval_s=0.0)
        self._has_qid |= has_qid
        self._has_field |= has_field

    def _check_not_shrunk(self) -> None:
        """Raise the mutation error if any backing file SHRANK since
        construction. Shrinkage is conclusive mutation evidence, and it
        must be caught by stat BEFORE a re-parse: the native engine
        reads files through mmap views, and touching pages past a new
        EOF is SIGBUS — a crash, not a catchable error (append and
        same-size rewrite still go to the read-path detectors)."""
        if self._ctor_sizes is None:
            return
        from dmlc_tpu.io.pagestore import stat_uri
        for path, size in self._ctor_sizes:
            try:
                now = stat_uri(path)[0]
            except (OSError, DMLCError):
                continue  # deleted/unstatable: the read path reports it
            if now < size:
                raise DMLCError(
                    f"ShardedRowBlockIter: backing file {path} shrank "
                    f"from {size} to {now} bytes — the underlying file "
                    "changed between epochs of one iterator "
                    "(deterministic replay is the contract; recreate "
                    "the iterator after mutating inputs)")

    def _restart_streams(self):
        its = []
        for p in self._parsers:
            p.before_first()
            its.append(self._rechunk(p))
        return its, [False] * len(its), [0] * len(its)

    def _next_row(self, its, done, counts) -> List[RowBlock]:
        sp = _trace.begin("shard.parse", "shard")
        try:
            return self._next_row_blocks(its, done, counts)
        finally:
            sp.end()

    def _next_row_blocks(self, its, done, counts) -> List[RowBlock]:
        row = []
        for i, it in enumerate(its):
            if done[i]:
                row.append(empty_block(self.index_dtype))
                continue
            try:
                blk = next(it)
                counts[i] += 1
                self._note_schema(blk.qid is not None,
                                  blk.field is not None)
                row.append(blk)
            except StopIteration:
                done[i] = True
                row.append(empty_block(self.index_dtype))
        self._schema_rounds += 1
        return row

    def _try_cache_epoch(self) -> Optional[List[List[RowBlock]]]:
        """Parse all local parts into cached RAW owned blocks, or None
        if the budget is exceeded (the fallback rewinds the parsers and
        runs the legacy per-round protocol, whose tee then spills the
        epoch's rounds to pages).

        Caching raw blocks (r6) instead of the r5 pad_to_bucket output
        shrinks the cache toward the data's true CSR bytes — several×
        below the padded size on short-row corpora — so more shards fit
        the same budget AND steady RSS tracks raw, not padded, size.
        The copy() detaches each block from any zero-copy engine lease
        (recycled on the parser's next()); padding moved to the serve-
        prefetch thread, where it overlaps the consumer's step."""
        budget = self.agreement_cache_bytes
        if not self._cache_precheck_ok():
            return None
        used = 0
        cached: List[List[RowBlock]] = []
        for p in self._parsers:
            p.before_first()
            part: List[RowBlock] = []
            for blk in self._rechunk(p):
                self._note_schema(blk.qid is not None,
                                  blk.field is not None)
                blk = blk.copy()
                used += blk.memory_cost_bytes()
                if used > budget:
                    return None
                part.append(blk)
            cached.append(part)
        return cached

    def _cache_precheck_ok(self) -> bool:
        """Cheap size pre-check: when the backing store is a plain local
        file whose local share already exceeds the budget (raw CSR
        blocks are rarely smaller than their text), skip the doomed
        in-memory caching attempt instead of parsing up to the budget
        only to throw it away — the replay tee then starts directly in
        spill mode. Near-boundary shards can still abort mid-pass —
        bounded waste the fallback re-parse accepts by design."""
        try:
            import os
            from dmlc_tpu.io.tpu_fs import local_path
            path = local_path(self._uri)
            if os.path.isfile(path):
                total = os.path.getsize(path)
                share = (total * len(self._my_parts)
                         // max(self._total_parts, 1))
                if share > self.agreement_cache_bytes:
                    return False
        except OSError:
            pass
        return True

    def _agree_first_epoch(self, cached_ok: bool, local_rounds: int):
        """ONE collective for epoch 1: gathers (did this process cache
        its shard?, its local round count, its observed qid/field
        schema). Returns (all processes cached, global rounds = max of
        counts — exhausted processes pad with empty batches up to it)
        and ORs the schema bits so every rank pads to one key set."""
        if jax.process_count() == 1:
            return cached_ok, max(local_rounds, 0)
        from jax.experimental import multihost_utils
        data = multihost_utils.process_allgather(
            np.array([1 if cached_ok else 0, local_rounds,
                      int(self._has_qid), int(self._has_field)],
                     dtype=np.int64))
        data = data.reshape(-1, 4)
        # collective OR bypasses _note_schema's flip warning: a peer
        # rank's keys arriving via agreement BEFORE this rank yields a
        # batch is the protocol working, not a mid-file flip
        self._has_qid |= bool(np.any(data[:, 2]))
        self._has_field |= bool(np.any(data[:, 3]))
        return bool(np.all(data[:, 0] == 1)), int(np.max(data[:, 1]))

    def _all_processes_done(self, local_done: bool) -> bool:
        """Collective agreement on stream end: with skewed shards, some
        processes exhaust early and must keep yielding empty batches until
        ALL are done (batch count is a collective contract). The same
        per-round collective ORs the observed qid/field schema, so a rank
        whose parts exhausted keeps padding with the keys the others
        carry (ADVICE r4 — the legacy path has no one-shot vote to ride)."""
        if jax.process_count() == 1:
            return local_done
        from jax.experimental import multihost_utils
        flags = multihost_utils.process_allgather(
            np.array([local_done, self._has_qid, self._has_field],
                     dtype=np.bool_))
        flags = flags.reshape(-1, 3)
        # collective OR: no flip warning (see _agree_first_epoch) — the
        # per-round agreement delivers peer keys before this round's
        # assembly, so batches stay uniformly keyed
        self._has_qid |= bool(np.any(flags[:, 1]))
        self._has_field |= bool(np.any(flags[:, 2]))
        return bool(np.all(flags[:, 0]))

    def _rechunk(self, parser) -> Iterator[RowBlock]:
        """Clip parser blocks to the (row_bucket, nnz_bucket) budget."""
        while parser.next():
            block = parser.value()
            start = 0
            while start < block.size:
                end = min(block.size, start + self.row_bucket)
                base = int(block.offset[start])
                while int(block.offset[end]) - base > self.nnz_bucket:
                    end -= 1
                check(end > start, "nnz_bucket smaller than one row")
                yield block.slice(start, end)
                start = end

    def _assemble_stacked(self, blocks: List[RowBlock]
                          ) -> Dict[str, np.ndarray]:
        # locally observed keys are sticky too: a round where every part
        # is an empty pad must still carry the keys earlier rounds did.
        # (Degenerate sources where qid/field first appears MID-file
        # change the batch structure at the discovery round in epoch 1,
        # and epochs 2+ carry the discovered keys from round 0 —
        # _note_schema logs the hazard once; real ranking/FFM corpora
        # tag every row.)
        self._note_schema(any(b.qid is not None for b in blocks),
                          any(b.field is not None for b in blocks))
        # the fused pad+stack: one in-place pass instead of per-part
        # pad_to_bucket dicts + np.stack — on the replay serve thread
        # this halves the memcpy per round, which IS the page-tier
        # throughput cap
        sp = _trace.begin("shard.stack", "shard")
        try:
            return stack_padded_rows(blocks, self.row_bucket,
                                     self.nnz_bucket, self._has_qid,
                                     self._has_field)
        finally:
            self.stack_s += sp.end()

    def _put(self, stacked: Dict[str, np.ndarray]) -> tuple:
        """(global batch, host (rows, nnz)) of one stacked round."""
        counts = (int(stacked["num_rows"].sum()),
                  int(stacked["num_nnz"].sum()))
        sp = _trace.begin("shard.put", "shard")
        try:
            return make_global_batch(stacked, self.mesh, self.axis), counts
        finally:
            sp.end()

    def _assemble(self, blocks: List[RowBlock]) -> tuple:
        return self._put(self._assemble_stacked(blocks))

    def engine_stats(self) -> Optional[Dict[str, Any]]:
        """The native engine counters summed over this process's part
        parsers (``wall_ns``: the slowest part's), beside ``stack_s``;
        None when no part parses natively."""
        engines = []
        for p in self._parsers:
            stats_fn = getattr(p, "stats", None)
            if stats_fn is not None:
                try:
                    engines.append(stats_fn())
                except Exception:  # noqa: BLE001 — telemetry must not kill
                    pass
        if not engines:
            return None
        return {k: (max if k == "wall_ns" else sum)(e[k] for e in engines)
                for k in engines[0]}

    def _note_tier(self, tier: str) -> None:
        """Stamp the tier serving this epoch; the per-iterator obs
        collector (``shard/<uri-base>``) surfaces it, so a stall
        report names each live iterator's OWN tier — a process-global
        gauge would show whichever iterator last started an epoch."""
        self.replay_tier = tier

    def _metrics(self) -> Dict[str, Any]:
        """obs.metrics collector shape (registered weakly at
        construction, pruned with the iterator)."""
        return {"replay_tier": self.replay_tier,
                "replay_epochs": self.replay_epochs,
                "page_replay_epochs": self.page_replay_epochs,
                "prefetch_depth": self.prefetch_depth}

    def __iter__(self) -> Iterator[Dict[str, jax.Array]]:
        self.stack_s = 0.0
        for batch, counts in self._epoch():
            self.last_counts = counts
            yield batch

    def _epoch(self) -> Iterator[tuple]:
        """One epoch of (global batch, host counts) pairs."""
        if self._rounds_per_epoch is None:
            self._note_tier("parse")
            yield from self._first_epoch_batches()
            return
        self._check_not_shrunk()
        if self._round_store is not None:
            import os
            store_file = getattr(self._round_store, "file", None)
            if (store_file is not None
                    and not os.path.exists(store_file.path)):
                # spill file vanished (external cleanup raced us): not a
                # data hazard — degrade to the re-parse path below and
                # let the tee re-earn a fresh store
                self._round_store = None
                self._fingerprint = None
        if self._round_store is not None:
            if (self._fingerprint is not None
                    and self._fingerprint == self._fingerprint_now()):
                self.replay_epochs += 1
                self._note_tier(self._round_store.tier)
                if self.replay_tier == "pages":
                    self.page_replay_epochs += 1
                yield from self._replay_store(self._round_store)
                return
            # backing files changed (or stopped stat-ing) since the
            # rounds were captured: the store is stale. Drop it (a page
            # tier deletes its spill file) and re-parse —
            # _steady_stream's count assertions then decide whether the
            # change was a hazard (truncation/rewrite raises) or benign
            # (appends are invisible by byte-range), exactly the
            # pre-replay semantics.
            store, self._round_store, self._fingerprint = \
                self._round_store, None, None
            self._was_pages = store.tier == "pages"
            store.drop()
        # Re-parse epoch; tee the raw rounds into a fresh replay store
        # (memory within budget, pages above it) so single-process
        # "auto" jobs (no epoch-1 cache) replay from epoch 3 on and a
        # mutated-then-stable file re-earns replay after one clean
        # re-parse epoch. A shard whose previous store was pages is
        # known over budget — skip the doomed memory accumulation.
        self._note_tier("parse")
        tee = self._make_tee(self._fingerprint_now(),
                             force_spill=self._was_pages)
        try:
            for blocks in self._steady_stream():
                tee.add_row(blocks)
                yield self._assemble(blocks)
            tee.commit(self, self._rounds_per_epoch)
        finally:
            tee.close()

    def close(self) -> None:
        """Release the replay store (a page-tier store deletes its
        spill file) and destroy the parsers. Safe to call twice; also
        invoked from __del__ so an abandoned iterator cannot leak spill
        files past process exit by accident."""
        if getattr(self, "_obs_key", None):
            from dmlc_tpu.obs.metrics import REGISTRY as _registry
            _registry.unregister(self._obs_key)
            self._obs_key = None
        store, self._round_store = self._round_store, None
        if store is not None:
            store.drop()
        for p in self._parsers:
            if hasattr(p, "destroy"):
                try:
                    p.destroy()
                except Exception:  # noqa: BLE001 — teardown
                    pass
        self._parsers = []

    def __del__(self):
        try:
            self.close()
        except Exception:  # noqa: BLE001 — interpreter shutdown
            pass
