"""Low-overhead, thread-aware trace recorder (span ring buffer).

The repo's ONE span API. A :class:`TraceRecorder` is a bounded ring
buffer of trace events — complete spans ("X"), instants ("i"), and
counter samples ("C") — each stamped with the recording thread. The
hot-path contract is:

- **off by default, near-zero cost when off**: instrumented code reads
  the module-global ``_recorder`` once (``rec = active()``) and skips
  all timing when it is None. The pipeline's stage probes go further
  and reuse the perf_counter pair they already measure, so a span
  costs one tuple-append beyond the telemetry the probe keeps anyway;
- **ring, not list**: a capped ``deque`` — a week-long run can leave
  tracing on and keep the LAST ``capacity`` events (``dropped`` counts
  the overwritten ones);
- **wall-anchored timestamps**: ts = wall-clock at recorder start plus
  a perf_counter delta, so traces from different processes of one gang
  merge onto a single timeline (``obs.export.merge_chrome_files``);
- **mirrored onto the profiler's clock**: while a ``jax.profiler``
  session collects, each span :func:`begin` opens is ALSO a live
  ``TraceMe`` on the thread doing the work, carrying its category as
  the ``cat`` argument, so it lands in the same xplane as the device's
  operations and a device idle gap can be put down to the program span
  open across it. Off, a site pays one ``is_enabled()`` check and one
  small object, and builds no ``TraceMe``. A live span opens and
  closes within one ``next()`` of the generator that does the work,
  never across a ``yield``.

Export to Chrome/Perfetto trace-event JSON lives in
:mod:`dmlc_tpu.obs.export`; ``trace_to(path)`` is the one-liner.
"""

from __future__ import annotations

import contextlib
import gc
import threading
import time
from collections import deque
from typing import Any, Dict, Iterator, List, Optional, Tuple

# the profiler's native span (jax.profiler.TraceAnnotation subclasses it)
from jaxlib._profiler import TraceMe as _TraceMe

__all__ = [
    "TraceRecorder", "active", "start", "stop", "trace_to",
    "trace_if_env", "span", "begin", "OpenSpan", "instant", "counter",
    "set_fallback", "clear_fallback", "fallback",
    "CAT_RPC_CLIENT", "CAT_RPC_SERVER",
]

# span categories for the cross-process RPC plane (obs.rpc): a client
# span is one attempt observed from the calling side, a server span is
# the serving handler's half. Both carry the serialized trace context
# in args — obs.export matches the pair into Perfetto flow events.
CAT_RPC_CLIENT = "rpc.client"
CAT_RPC_SERVER = "rpc.server"

# event tuples: (ph, name, cat, t_s, dur_s, tid, args)
#   ph "X": t_s = span start (perf_counter), dur_s = duration
#   ph "i": instant at t_s
#   ph "C": counter sample at t_s, args = {series: number}
_Event = Tuple[str, str, str, float, float, int, Optional[dict]]


class TraceRecorder:
    """Bounded ring buffer of trace events, thread-aware."""

    def __init__(self, capacity: int = 1 << 20):
        self._events: deque = deque(maxlen=int(capacity))
        self.capacity = int(capacity)
        self.recorded = 0          # total ever recorded (>=len => drops)
        # wall anchor: ts_us(e) = (wall0 + (t - perf0)) * 1e6 — stable
        # across processes on one host, perf_counter resolution within
        self.wall0_s = time.time()
        self.perf0_s = time.perf_counter()
        self._threads: Dict[int, str] = {}
        self._lock = threading.Lock()

    # -- recording (hot path: one counted append per event; the lock
    # guards only the `recorded` read-modify-write — `+= 1` from
    # concurrent producer/consumer threads would lose increments and
    # under-report `dropped`, making a truncated trace look complete)

    def _note_thread(self) -> int:
        t = threading.current_thread()
        ident = t.ident or 0
        if ident not in self._threads:
            with self._lock:
                self._threads[ident] = t.name
        return ident

    def _count(self) -> None:
        with self._lock:
            self.recorded += 1

    def complete(self, name: str, t0_s: float, dur_s: float,
                 cat: str = "", args: Optional[dict] = None) -> None:
        """One finished span: t0_s is perf_counter() at span start."""
        self._count()
        self._events.append(
            ("X", name, cat, t0_s, dur_s, self._note_thread(), args))

    def instant(self, name: str, cat: str = "",
                args: Optional[dict] = None) -> None:
        self._count()
        self._events.append(("i", name, cat, time.perf_counter(), 0.0,
                             self._note_thread(), args))

    def counter(self, name: str, values: Dict[str, Any],
                cat: str = "") -> None:
        """One sample of a counter track (numeric series only)."""
        nums = {k: v for k, v in values.items()
                if isinstance(v, (int, float)) and not isinstance(v, bool)}
        if not nums:
            return
        self._count()
        self._events.append(("C", name, cat, time.perf_counter(), 0.0,
                             self._note_thread(), nums))

    # -- recording on behalf of NON-Python threads (the native engine's
    # span ring drains through here: events carry the engine's own small
    # thread ids, far below any pthread ident, so tracks never collide)

    def complete_at(self, name: str, t0_s: float, dur_s: float, tid: int,
                    cat: str = "", args: Optional[dict] = None) -> None:
        """One finished span attributed to an explicit thread id."""
        self._count()
        self._events.append(("X", name, cat, t0_s, dur_s, int(tid), args))

    def instant_at(self, name: str, t_s: float, tid: int, cat: str = "",
                   args: Optional[dict] = None) -> None:
        """One instant event attributed to an explicit thread id."""
        self._count()
        self._events.append(("i", name, cat, t_s, 0.0, int(tid), args))

    def name_thread(self, tid: int, name: str) -> None:
        """Register a display name for an explicit thread id (first
        registration wins, matching _note_thread's behavior)."""
        with self._lock:
            self._threads.setdefault(int(tid), name)

    # -- reading

    @property
    def dropped(self) -> int:
        return max(0, self.recorded - len(self._events))

    def events(self) -> List[_Event]:
        return list(self._events)

    def thread_names(self) -> Dict[int, str]:
        with self._lock:
            return dict(self._threads)

    def ts_us(self, t_s: float) -> float:
        """perf_counter time → wall-anchored microseconds."""
        return (self.wall0_s + (t_s - self.perf0_s)) * 1e6


# module-global active recorder: None = tracing off. Hot paths read
# this ONCE per operation (`rec = active()`); everything else no-ops.
_recorder: Optional[TraceRecorder] = None
# the always-on FALLBACK ring (obs.flight installs its small crash
# ring here): it serves as the active recorder whenever no explicit
# trace is running, so instrumented sites still read ONE global —
# start() displaces it for the explicit trace, stop() restores it
_fallback: Optional[TraceRecorder] = None


def active() -> Optional[TraceRecorder]:
    """The installed recorder, or None when tracing is off."""
    return _recorder


def fallback() -> Optional[TraceRecorder]:
    """The installed always-on fallback ring (obs.flight), if any."""
    return _fallback


def _sync_native(on: bool) -> None:
    """Mirror the Python tracing on/off global into the native engine's
    span-ring flag — only when the engine library is ALREADY loaded
    (tracing must never trigger a native build/load)."""
    try:
        from dmlc_tpu.native import bindings
        if bindings._lib is not None:
            bindings._lib.dtp_trace_set_enabled(1 if on else 0)
    except Exception:  # noqa: BLE001 — telemetry must not raise
        pass


def set_fallback(rec: TraceRecorder) -> None:
    """Install ``rec`` as the always-on fallback ring. It becomes the
    active recorder immediately unless an explicit trace is running
    (that trace keeps recording; ``rec`` takes over at its stop())."""
    global _recorder, _fallback
    if _recorder is None or _recorder is _fallback:
        _recorder = rec
    _fallback = rec
    _sync_native(_recorder is not None)


def clear_fallback() -> Optional[TraceRecorder]:
    """Remove the fallback ring (obs.flight uninstall); returns it."""
    global _recorder, _fallback
    rec, _fallback = _fallback, None
    if _recorder is rec:
        _recorder = None
    _sync_native(_recorder is not None)
    return rec


def start(capacity: int = 1 << 20) -> TraceRecorder:
    """Install a fresh global recorder. Replacing a live one discards
    everything it held — say so, because the outer ``trace_to`` will
    then skip its export and the silent combination reads as "the
    trace was empty" instead of "two tracers fought". (Displacing the
    always-on fallback ring is the designed interplay, not a fight:
    no warning, and stop() reinstates it.)"""
    global _recorder
    if _recorder is not None and _recorder is not _fallback:
        from dmlc_tpu.obs.log import warn_limited
        warn_limited(
            "trace-recorder-replaced",
            f"obs.trace.start(): replacing an active recorder "
            f"({len(_recorder.events())} buffered events discarded; "
            "an enclosing trace_to() will not export) — nest trace "
            "scopes, don't overlap them", min_interval_s=60.0,
            all_ranks=True)
    _recorder = TraceRecorder(capacity)
    _sync_native(True)
    return _recorder


def stop() -> Optional[TraceRecorder]:
    """Uninstall and return the active EXPLICIT recorder, reinstating
    the always-on fallback ring (if one is installed). When only the
    fallback is active it stays installed and None is returned — use
    :func:`clear_fallback` to take it down."""
    global _recorder
    rec = _recorder
    if rec is None or rec is _fallback:
        return None
    _recorder = _fallback
    _sync_native(_recorder is not None)
    return rec


@contextlib.contextmanager
def trace_to(path: str, capacity: int = 1 << 20) -> Iterator[TraceRecorder]:
    """Record for the duration of the block and export Chrome
    trace-event JSON to ``path`` on exit (even on error)."""
    from dmlc_tpu.obs.export import write_chrome
    rec = start(capacity)
    try:
        yield rec
    finally:
        if stop() is rec:
            write_chrome(rec, path)


# True while a jax.profiler session collects (one native call, no
# allocation)
_profiling = _TraceMe.is_enabled
_now = time.perf_counter


def _live(name: str, cat: str):
    """``name`` opened on the profiler's timeline, on the calling
    thread (check :func:`_profiling` first: off, build nothing)."""
    tm = _TraceMe(name, cat=cat)
    tm.__enter__()
    return tm


class OpenSpan:
    """A span :func:`begin` opened. :meth:`end` closes it on the active
    recorder and the profiler's timeline alike, and returns its
    seconds: the one perf_counter pair a site's probe reads too."""

    __slots__ = ("name", "cat", "args", "rec", "tm", "t0")

    def __init__(self, name: str, cat: str = "",
                 args: Optional[dict] = None):
        self.name = name
        self.cat = cat
        self.args = args
        self.rec = _recorder
        self.tm = _live(name, cat) if _profiling() else None
        self.t0 = _now()

    def end(self, name: Optional[str] = None, **meta: Any) -> float:
        """Close on the thread that opened it. ``name`` renames the
        recorder's span (the profiler's keeps the name it opened with);
        ``meta`` goes on both, as arguments."""
        dt = _now() - self.t0
        if self.tm is not None:
            if meta:
                self.tm.set_metadata(**meta)
            self.tm.__exit__(None, None, None)
        if self.rec is not None:
            args = self.args
            if meta:
                args = {**args, **meta} if args else meta
            self.rec.complete(name or self.name, self.t0, dt, self.cat, args)
        return dt


# begin(name, cat="", args=None) -> OpenSpan: open a span on the active
# recorder and, while a profiler collects, on its timeline; close it
# with OpenSpan.end() in a ``finally``. With neither on it only times:
# one global read, one is_enabled() check, its perf_counter pair. The
# hot path's form of span(): no generator frame, and no call beyond
# the constructor's.
begin = OpenSpan


@contextlib.contextmanager
def span(name: str, cat: str = "",
         args: Optional[dict] = None) -> Iterator[None]:
    """Record the block as one complete span (see :func:`begin`)."""
    sp = begin(name, cat, args)
    try:
        yield
    finally:
        sp.end()


def instant(name: str, cat: str = "", args: Optional[dict] = None) -> None:
    rec = _recorder
    if rec is not None:
        rec.instant(name, cat, args)


def counter(name: str, values: Dict[str, Any], cat: str = "") -> None:
    rec = _recorder
    if rec is not None:
        rec.counter(name, values, cat)


# -- garbage collections as spans: ``python.gc``, one per collection,
# on the thread that triggered it (a collection runs to its end on that
# thread, so start/stop pair up), on the profiler's timeline only: a
# collection can start inside any allocation, a recorder's locked
# sections included, so the hook never touches a recorder. Off, each
# phase costs one check.
_gc_open = None


def _gc_span(phase: str, info: Dict[str, Any]) -> None:
    global _gc_open
    if phase == "start":
        if _profiling():
            _gc_open = _live("python.gc", "python")
        return
    tm, _gc_open = _gc_open, None
    if tm is not None:
        tm.set_metadata(generation=info.get("generation"),
                        collected=info.get("collected"))
        tm.__exit__(None, None, None)


if _gc_span not in gc.callbacks:
    gc.callbacks.append(_gc_span)


@contextlib.contextmanager
def trace_if_env(trace_dir: Optional[str] = None) -> Iterator[None]:
    """Gang-worker tracing hook: when ``DMLC_TPU_TRACE_DIR`` is set
    (``parallel.launch.launch_local(trace_dir=...)`` sets it for every
    worker) — or a dir is passed explicitly — record for the duration
    of the block and export a rank-tagged trace file into that dir;
    otherwise a no-op. ``merge_gang_traces`` stitches the files."""
    import os
    d = trace_dir or os.environ.get("DMLC_TPU_TRACE_DIR")
    if not d:
        yield
        return
    from dmlc_tpu.obs.export import worker_rank
    rank = worker_rank()
    tag = f"rank{rank}" if rank is not None else f"pid{os.getpid()}"
    os.makedirs(d, exist_ok=True)
    with trace_to(os.path.join(d, f"trace-{tag}.json")):
        yield
