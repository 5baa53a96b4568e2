"""Per-stage telemetry for compiled pipelines.

Every stage boundary in a compiled :class:`~dmlc_tpu.pipeline.Pipeline`
carries a :class:`StageProbe`. The probe sits at the pull site: each time
the downstream consumer asks the stage for an item it records

- **wait time** — seconds the consumer blocked waiting for the stage to
  deliver (the stage's un-overlapped latency),
- **items / rows / bytes** — volume counters for throughput,
- **queue occupancy** — for queue-backed stages (``prefetch``, the
  parser's chunk prefetch), a per-pull sample of ``qsize/capacity`` so
  the autotuner can tell producer-bound (queue empty) from
  consumer-bound (queue full) stages.

``snapshot()`` freezes one epoch of probes into a plain-JSON dict with a
versioned schema (``PIPELINE_STATS_SCHEMA``) — the shape
``obs.analyze.attribute`` reads and tests/test_pipeline.py pins.

Stage-specific ``extra`` fields (additive, schema version unchanged):

- parse: ``bytes_read``, ``engine`` (native engine stats)
- to_device: ``xfer_wait_s`` (transfer-drain wait)
- cache / shard (r6): ``replay_tier`` — which tier served the epoch
  ("parse" | "memory" | "pages"); shard also carries ``replay_epochs``
  / ``page_replay_epochs`` counters and ``serve`` (the serve-prefetch
  queue's producer stats: items produced, seconds blocked on a full
  queue). The autotuner keys its tier-flip gate off ``replay_tier``.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy as np

__all__ = ["StageProbe", "snapshot", "PIPELINE_STATS_SCHEMA"]

# bump when snapshot()'s shape changes incompatibly
PIPELINE_STATS_SCHEMA = 1


def _host_count(v) -> int:
    """Sum of a host count field; 0 for a device array, which is never
    read back here (telemetry must not wait on the device)."""
    if isinstance(v, (np.ndarray, np.generic)):
        return int(v.sum())
    return v if isinstance(v, int) else 0


def _item_stats(item) -> tuple:
    """(rows, nnz, bytes) of one host pipeline item: RowBlock, array
    dict, or opaque (counted as zeros — items is always exact). Count
    fields held on a device are not read (they count as zeros): the
    stages that deliver device arrays report the host counts they had
    before the transfer instead (``_RunnerBase.item_stats``)."""
    # RowBlock duck-type: .offset/.size/.memory_cost_bytes
    cost = getattr(item, "memory_cost_bytes", None)
    if cost is not None:
        return int(item.size), int(item.nnz), int(cost())
    if isinstance(item, dict):
        rows = nnz = 0
        nbytes = 0
        for k, v in item.items():
            nb = getattr(v, "nbytes", None)
            if nb is not None:
                nbytes += int(nb)
        nr = item.get("num_rows")
        if nr is not None:
            rows = _host_count(nr)
        elif "label" in item and hasattr(item["label"], "shape"):
            shape = item["label"].shape
            rows = int(np.prod(shape)) if shape else 0
        nz = item.get("num_nnz")
        if nz is not None:
            nnz = _host_count(nz)
        elif "index" in item and hasattr(item["index"], "shape"):
            nnz = int(np.prod(item["index"].shape))
        return rows, nnz, nbytes
    return 0, 0, 0


class StageProbe:
    """Accumulates one epoch of boundary measurements for one stage."""

    __slots__ = ("name", "kind", "items", "rows", "nnz", "bytes",
                 "wait_s", "occupancy_sum", "occupancy_samples",
                 "queue_cap", "extra")

    def __init__(self, name: str, kind: str):
        self.name = name
        self.kind = kind
        self.extra: Dict[str, Any] = {}
        self.reset()

    def reset(self) -> None:
        self.items = 0
        self.rows = 0
        self.nnz = 0
        self.bytes = 0
        self.wait_s = 0.0
        self.occupancy_sum = 0
        self.occupancy_samples = 0
        self.queue_cap: Optional[int] = None
        self.extra = {}

    def record(self, counts: tuple, wait_s: float, queue=None) -> None:
        """One delivered item: wait seconds + its (rows, nnz, bytes) +
        queue sample."""
        self.wait_s += wait_s
        self.items += 1
        rows, nnz, nbytes = counts
        self.rows += rows
        self.nnz += nnz
        self.bytes += nbytes
        if queue is not None:
            self.occupancy_sum += queue.qsize()
            self.occupancy_samples += 1
            self.queue_cap = queue.capacity

    def record_wait_only(self, wait_s: float) -> None:
        """Terminal wait (the pull that returned end-of-stream)."""
        self.wait_s += wait_s

    def as_dict(self, wall_s: float) -> Dict[str, Any]:
        occ = (self.occupancy_sum / self.occupancy_samples
               if self.occupancy_samples else None)
        out: Dict[str, Any] = {
            "name": self.name,
            "kind": self.kind,
            "items": self.items,
            "rows": self.rows,
            "nnz": self.nnz,
            "bytes": self.bytes,
            "wait_s": round(self.wait_s, 6),
            "wait_frac": (round(self.wait_s / wall_s, 4)
                          if wall_s > 0 else None),
            "throughput_gbps": (round(self.bytes / wall_s / 1e9, 4)
                                if wall_s > 0 else None),
            "rows_per_s": (round(self.rows / wall_s, 1)
                           if wall_s > 0 else None),
            "queue_depth_mean": (round(occ, 2) if occ is not None
                                 else None),
            "queue_cap": self.queue_cap,
            "queue_occupancy": (round(occ / self.queue_cap, 3)
                                if occ is not None and self.queue_cap
                                else None),
        }
        if self.extra:
            # stage-specific fields (device xfer wait, engine stats, ...)
            out["extra"] = dict(self.extra)
        return out


def snapshot(probes: List[StageProbe], wall_s: float, epoch: int,
             knobs: Optional[Dict[str, int]] = None) -> Dict[str, Any]:
    """Freeze one epoch of probes into the versioned stats dict."""
    return {
        "schema": PIPELINE_STATS_SCHEMA,
        "epoch": epoch,
        "wall_s": round(wall_s, 4),
        "stages": [p.as_dict(wall_s) for p in probes],
        "knobs": dict(knobs or {}),
    }
