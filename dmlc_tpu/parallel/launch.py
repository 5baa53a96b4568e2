"""Job launcher + rendezvous: the TPU-native `tracker/` equivalent.

Reference: tracker/dmlc_tracker/{submit,opts,tracker,local,ssh,mpi}.py —
dmlc-submit CLI, RabitTracker rendezvous (rank assignment + ring/tree
topologies over sockets), env-var contract (DMLC_TRACKER_URI, DMLC_ROLE,
DMLC_TASK_ID, DMLC_NUM_WORKER, ...).

TPU-native mapping (SURVEY.md §2.4/§5.8): the entire tracker job — workers
find a coordinator, get a rank, learn the world size — is
jax.distributed.initialize(coordinator_address, num_processes,
process_id). This module provides:

- the env contract (DMLC_TPU_COORDINATOR_URI/NUM_WORKER/TASK_ID, with the
  reference's DMLC_* names accepted as aliases so reference-style
  launchers keep working),
- ``init_from_env()`` — worker-side rendezvous,
- ``launch_local()`` — N local processes (the reference's --cluster local,
  and how multi-host tests run without a cluster),
- ``launch_ssh()`` — command generation for bare-metal clusters,
- ring/tree topology helpers for API parity with RabitTracker
  (get_ring/get_tree/get_link_map). On TPU these are informational —
  XLA picks collective topology — but downstream code that asks for
  them keeps working.
"""

from __future__ import annotations

import os
import shlex
import subprocess
import sys
from typing import Dict, List, Optional, Sequence, Tuple

from dmlc_tpu.utils.logging import DMLCError, check

__all__ = [
    "worker_envs", "ps_envs", "get_role", "init_from_env", "finalize",
    "launch_local", "launch_ssh", "get_ring", "get_tree", "get_link_map",
    "find_free_port", "find_free_ports", "merge_gang_traces", "main",
    "rendezvous_envs",
]

# workers that wrap their run in obs.trace.trace_if_env() export a
# rank-tagged Chrome trace into this dir (launch_local(trace_dir=...))
ENV_TRACE_DIR = "DMLC_TPU_TRACE_DIR"
# live-telemetry env contract (launch_local(serve_ports=...) /
# launch_local(flight_dir=...)): workers opt in with one call each —
# obs.serve.serve_if_env() and obs.flight.install_if_env()
ENV_SERVE_PORT = "DMLC_TPU_SERVE_PORT"    # this worker's status port
ENV_SERVE_PORTS = "DMLC_TPU_SERVE_PORTS"  # comma-joined gang ports
ENV_FLIGHT_DIR = "DMLC_TPU_FLIGHT_DIR"    # crash-bundle output dir
# analysis-plane env contract (launch_local(history_s=...) /
# launch_local(gang_poll_s=...)): workers opt in with one call each —
# obs.timeseries.install_if_env() and obs.aggregate.install_if_env()
ENV_HISTORY_S = "DMLC_TPU_HISTORY_S"      # time-series sample period
ENV_GANG_POLL_S = "DMLC_TPU_GANG_POLL_S"  # rank-0 gang-poll period
ENV_PROFILE_HZ = "DMLC_TPU_PROFILE_HZ"    # sampling-profiler rate
#   (launch_local(profile_hz=...); obs.profile.install_if_env())
ENV_CONTROL = "DMLC_TPU_CONTROL"          # verdict-driven controller
#   (launch_local(control=True); obs.control.install_if_env())
ENV_SCHED = "DMLC_TPU_SCHED"              # multi-tenant scheduler
#   (launch_local(scheduler=...); pipeline.scheduler.install_if_env())
ENV_SLO = "DMLC_TPU_SLO"                  # declared SLO objectives
#   (launch_local(slo=...); obs.slo.install_if_env())
# resilience contracts (dmlc_tpu.resilience): launch_local(faults=...)
# sets DMLC_TPU_FAULTS for every member; the gang supervisor sets
# DMLC_TPU_ATTEMPT (alias DMLC_NUM_ATTEMPT — the reference's rejoin
# counter) to 0 on first spawn and bumps it per restart
# elastic-gang rendezvous contract (dmlc_tpu.rendezvous):
# launch_local(rendezvous=True) starts the membership service and
# exports DMLC_TPU_RNDV_URI/PORT (+ DMLC_TPU_RNDV_GANG); workers join
# with one rendezvous.install_if_env() line

# env contract (reference: slave_envs in tracker.py)
ENV_COORD = "DMLC_TPU_COORDINATOR_URI"
ENV_NWORKER = "DMLC_TPU_NUM_WORKER"
ENV_TASK_ID = "DMLC_TPU_TASK_ID"
# reference-name aliases accepted on read
_ALIASES = {
    ENV_COORD: ["DMLC_TRACKER_URI"],
    ENV_NWORKER: ["DMLC_NUM_WORKER"],
    ENV_TASK_ID: ["DMLC_TASK_ID"],
}


def _getenv(name: str) -> Optional[str]:
    v = os.environ.get(name)
    if v:
        return v
    for alias in _ALIASES.get(name, []):
        v = os.environ.get(alias)
        if v:
            return v
    return None


def find_free_port(host: str = "127.0.0.1") -> int:
    return find_free_ports(1, host)[0]


def find_free_ports(n: int, host: str = "127.0.0.1") -> List[int]:
    """``n`` distinct free ports, all probe sockets held open until
    chosen (ADVICE r5 — back-to-back single-port probes can collide).
    The implementation lives with the package's other raw-socket code
    in ``rendezvous/service.py`` (the scripts/lint.py socket gate);
    this re-export keeps the historical launcher API."""
    from dmlc_tpu.rendezvous.service import probe_free_ports
    return probe_free_ports(n, host)


def worker_envs(coordinator: str, num_workers: int,
                task_id: int) -> Dict[str, str]:
    """The env block handed to each worker (reference: slave_envs +
    per-worker DMLC_TASK_ID). Reference names are set too, for
    downstream code that reads them."""
    check(":" in coordinator,
          f"coordinator must be host:port, got {coordinator!r}")
    return {
        ENV_COORD: coordinator,
        ENV_NWORKER: str(num_workers),
        ENV_TASK_ID: str(task_id),
        "DMLC_TRACKER_URI": coordinator.rsplit(":", 1)[0],
        "DMLC_TRACKER_PORT": coordinator.rsplit(":", 1)[1],
        "DMLC_NUM_WORKER": str(num_workers),
        "DMLC_TASK_ID": str(task_id),
        "DMLC_ROLE": "worker",
    }


def ps_envs(root_uri: str, root_port: int, num_workers: int,
            num_servers: int, role: str,
            task_id: Optional[int] = None) -> Dict[str, str]:
    """The parameter-server half of the reference env contract
    (reference: tracker.py PSTracker — DMLC_PS_ROOT_URI/PORT,
    DMLC_ROLE in scheduler|server|worker, DMLC_NUM_SERVER/WORKER).

    The TPU framework itself has no parameter-server architecture (XLA
    collectives over ICI/DCN replace push/pull — SURVEY §5.8), but
    PS-Lite-style DOWNSTREAM code launched through this tracker expects
    these names; launch_local(num_servers=...) spawns the full role set
    with this contract so such code finds its scheduler."""
    check(role in ("scheduler", "server", "worker"),
          f"unknown PS role {role!r}")
    out = {
        "DMLC_PS_ROOT_URI": root_uri,
        "DMLC_PS_ROOT_PORT": str(root_port),
        "DMLC_NUM_SERVER": str(num_servers),
        "DMLC_NUM_WORKER": str(num_workers),
        "DMLC_ROLE": role,
    }
    if task_id is not None:
        out["DMLC_TASK_ID"] = str(task_id)
    return out


def get_role() -> str:
    """This process's tracker role (reference: DMLC_ROLE). 'worker' when
    unset — only launch_local(num_servers>0) / PS-style launchers create
    the other roles. Branch on this BEFORE init_from_env: scheduler and
    server processes are not part of the jax.distributed worker gang."""
    return os.environ.get("DMLC_ROLE", "worker")


def init_from_env(force: bool = False) -> Tuple[int, int]:
    """Worker-side rendezvous: jax.distributed.initialize from the env
    contract. Returns (process_id, num_processes). No-op (returning
    jax's current values) when the env is absent — single-process mode.
    """
    import jax
    check(get_role() == "worker",
          f"init_from_env joins the WORKER gang; this process is a "
          f"{get_role()!r} (branch on get_role() first — PS scheduler/"
          f"server processes run their own control plane)")
    coord = _getenv(ENV_COORD)
    if coord is None and not force:
        return jax.process_index(), jax.process_count()
    check(coord is not None, f"{ENV_COORD} not set")
    nworker = int(_getenv(ENV_NWORKER) or "1")
    task_id = int(_getenv(ENV_TASK_ID) or "0")
    jax.distributed.initialize(coordinator_address=coord,
                               num_processes=nworker,
                               process_id=task_id)
    return task_id, nworker


def finalize() -> None:
    """Synchronize all processes and shut the rendezvous down cleanly.

    Call at worker exit: without the barrier the coordinator (rank 0) can
    exit while peers are mid-handshake, turning a clean run into nonzero
    exit codes (the reference tracker solves this with its N-"shutdown"
    accept loop in tracker.py)."""
    import jax
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices("dmlc_tpu_finalize")
        jax.distributed.shutdown()


def launch_local(num_workers: int, command: Sequence[str],
                 env: Optional[Dict[str, str]] = None,
                 coordinator: Optional[str] = None,
                 timeout: Optional[float] = None,
                 num_servers: int = 0,
                 trace_dir: Optional[str] = None,
                 serve_ports=None,
                 flight_dir: Optional[str] = None,
                 history_s: Optional[float] = None,
                 gang_poll_s: Optional[float] = None,
                 profile_hz: Optional[float] = None,
                 control: Optional[bool] = None,
                 scheduler=None,
                 slo=None,
                 restart_policy=None,
                 faults=None,
                 rendezvous: bool = False,
                 heartbeat_grace_s: Optional[float] = None) -> List[int]:
    """Run N worker processes on this host (reference: local.py).

    It binds no chip to a worker. On a TPU host a chip belongs to one
    process at a time, so start one chip-holding process per host and
    nothing more: every other worker says ``JAX_PLATFORMS=cpu`` in
    ``env``, and the launcher's own process stays off the chip while
    a chip-holding worker runs.

    With ``num_servers > 0`` (reference: dmlc-submit --num-servers +
    PSTracker), additionally spawns ONE scheduler and ``num_servers``
    server processes running the same command under the PS env contract
    (DMLC_PS_ROOT_URI/PORT, DMLC_ROLE) — the command branches on
    ``get_role()``. Workers carry BOTH contracts; the jax gang is
    workers-only.

    The gang is owned by a :class:`dmlc_tpu.resilience.GangSupervisor`:
    a worker that **exits 0 early** is a finished member (the gang
    keeps running; PS service roles that outlive every worker are
    terminated cleanly), a worker that **dies** (nonzero exit or
    signal) kills the gang on first failure — unless
    ``restart_policy`` (a :class:`dmlc_tpu.resilience.RestartPolicy`,
    or an int = max restarts per worker) is given, in which case the
    dead worker is respawned with its SAME coordinates and a bumped
    ``DMLC_TPU_ATTEMPT`` (alias ``DMLC_NUM_ATTEMPT``) up to the
    budget, exploiting the determinism contract (tests/test_elastic).
    Budget exhausted = prompt gang teardown (plus a launcher-side
    flight bundle when ``flight_dir`` is set), never a hang. Restarts
    surface as ``dmlc_resilience_restart_total`` on the launcher's
    /metrics and as ``gang/restart/<member>`` instants on the merged
    gang trace.

    ``faults`` (a spec string or :class:`dmlc_tpu.resilience.FaultPlan`)
    hands every member the ``DMLC_TPU_FAULTS`` chaos contract — members
    opt in with one ``resilience.inject.install_if_env()`` call, and
    the seeded plan makes every run provoke identical failures.

    ``trace_dir`` hands every worker the obs tracing contract
    (``DMLC_TPU_TRACE_DIR``): workers that wrap their run in
    ``dmlc_tpu.obs.trace.trace_if_env()`` each export a rank-tagged
    Chrome trace there, and on a clean gang exit the per-worker files
    are merged into ``<trace_dir>/trace-gang.json`` — one Perfetto
    timeline, one process row per rank.

    ``serve_ports`` wires the LIVE telemetry plane (dmlc_tpu.obs.serve):
    a list of one port per worker (or ``True`` to probe free ones) hands
    rank *i* ``DMLC_TPU_SERVE_PORT=ports[i]`` — workers that call
    ``obs.serve.serve_if_env()`` answer /metrics, /healthz, /stacks and
    /trace WHILE the gang runs — plus the full comma-joined list in
    ``DMLC_TPU_SERVE_PORTS`` so rank 0 (or anyone) can
    ``obs.serve.scrape_gang()`` the live processes into one merged
    snapshot. Pass explicit ports when the launcher itself will scrape.
    The same two variables ARE the gang's peer DATA plane
    (docs/remote_io.md "Peer tier"): each rank's server also answers
    ``/pages/<entry>``, and the objstore read path
    (``dmlc_tpu.io.objstore.peer``) derives the gang topology from the
    exported port list — a serving gang hydrates ``obj://`` pages from
    its peers ahead of the wire with zero extra wiring (give each rank
    its own ``DMLC_TPU_PAGESTORE_DIR`` when they share a host).

    ``flight_dir`` hands every worker the crash flight-recorder
    contract (``DMLC_TPU_FLIGHT_DIR``): workers that call
    ``obs.flight.install_if_env()`` leave a post-mortem bundle there
    when they die badly (uncaught exception, fatal signal, confirmed
    stall) — the black box for the gang member that took everyone down.

    ``history_s`` hands every worker the time-series contract
    (``DMLC_TPU_HISTORY_S``): workers that call
    ``obs.timeseries.install_if_env()`` sample their metrics registry
    at that period into the shared bounded ring — served live at
    ``/history``, attached to stall reports and crash bundles.

    ``gang_poll_s`` sets ``DMLC_TPU_GANG_POLL_S`` on RANK 0 ONLY:
    with ``serve_ports`` also wired, a rank-0
    ``obs.aggregate.install_if_env()`` call polls every peer's
    ``/metrics.json`` at that period into one gang timeline (per-rank
    series + sum/min/max rollups + explicit unreachable gaps), served
    at rank 0's ``/gang``.

    ``profile_hz`` hands every worker the sampling-profiler contract
    (``DMLC_TPU_PROFILE_HZ``): workers that call
    ``obs.profile.install_if_env()`` run the continuous sampler at
    that rate — merged Python+native flamegraphs served at
    ``/profile``, attached to stall reports and crash bundles
    (``profile.txt``), and feeding ``hot_frames`` verdict evidence.

    ``control=True`` hands every worker the verdict-driven control
    plane (``DMLC_TPU_CONTROL``): workers that call
    ``obs.control.install_if_env()`` run the between-epoch controller
    — the ``/analyze`` verdict picks WHICH knob family moves, every
    decision (including freezes and no-ops) lands in the per-rank
    decision ledger served at ``/control``, rendered by ``obsctl
    control``, aggregated gang-wide, and attached to flight bundles
    as ``control.json``.

    ``scheduler=True`` (or a ``DMLC_TPU_SCHED`` option string such
    as ``"quantum=4,queue=48"``) hands every worker the multi-tenant
    pipeline scheduler contract: workers that call
    ``pipeline.scheduler.install_if_env()`` share their process's
    thread/queue budgets across tenants (``Pipeline.build(tenant=...)``)
    with DRR pull credits, admission control, and per-tenant rows at
    ``/tenants`` (rendered by ``obsctl tenants``).

    ``slo=True`` (or a ``DMLC_TPU_SLO`` declaration string such as
    ``"name=ingest,metric=tenant.ingest.batch_s,target=0.15"``) hands
    every worker the SLO contract (:mod:`dmlc_tpu.obs.slo`): workers
    that call ``obs.slo.install_if_env()`` judge declared objectives
    live — windowed attainment, error-budget remaining, and
    fast/slow burn alerts at ``/slo`` (rendered by ``obsctl slo``),
    rolled up gang-wide on rank 0's ``/gang``, attached to flight
    bundles as ``slo.json``, and surfaced as ``slo`` verdicts on
    ``/analyze``. Tenants can also declare objectives through the
    scheduler string (``scheduler="slo.victim=0.15:300:0.01"``).

    ``rendezvous=True`` makes the gang ELASTIC (docs/rendezvous.md):
    the launcher starts a :class:`dmlc_tpu.rendezvous.RendezvousService`
    and exports ``DMLC_TPU_RNDV_URI/PORT`` (+ the gang name) — workers
    that call ``dmlc_tpu.rendezvous.install_if_env()`` join, heartbeat,
    and learn roster changes through the membership epoch. The
    supervisor reports deaths to the service (epoch bumps immediately,
    not after the heartbeat grace), and a worker whose restart budget
    is exhausted SHRINKS the gang instead of killing it — survivors
    re-derive shard ownership (``rendezvous.elastic``) and resume
    mid-epoch from exchanged progress. ``heartbeat_grace_s`` tunes
    the service's silent-member death window.

    Returns the list of exit codes (workers first in task-id order,
    then scheduler, then servers). Raises if any process fails (in an
    elastic rendezvous gang, a shrink is NOT a failure: dead members'
    nonzero codes are returned for inspection instead).
    """
    check(num_workers >= 1, "num_workers must be >= 1")
    check(num_servers >= 0, "num_servers must be >= 0")
    if serve_ports is True:
        serve_ports = find_free_ports(num_workers)
    if serve_ports is not None:
        serve_ports = [int(p) for p in serve_ports]
        check(len(serve_ports) == num_workers,
              f"serve_ports needs one port per worker "
              f"({len(serve_ports)} != {num_workers})")
    if flight_dir is not None:
        os.makedirs(flight_dir, exist_ok=True)
    if trace_dir is not None:
        import glob
        os.makedirs(trace_dir, exist_ok=True)
        # stale trace-*.json from a previous gang (e.g. a 4-worker run
        # reusing a 2-worker run's dir) would merge as ghost rank rows
        # on the new timeline — this launch owns the dir's trace files
        for stale in glob.glob(os.path.join(trace_dir, "trace-*.json")):
            try:
                os.remove(stale)
            except OSError:
                pass
    ps_root: Optional[Tuple[str, int]] = None
    if coordinator is None and num_servers > 0:
        # one probe pass holding both sockets: back-to-back single-port
        # probes could hand the coordinator and the PS root the SAME
        # port (ADVICE r5)
        coord_port, ps_port = find_free_ports(2)
        coordinator = f"127.0.0.1:{coord_port}"
        ps_root = ("127.0.0.1", ps_port)
    else:
        if coordinator is None:
            coordinator = f"127.0.0.1:{find_free_port()}"
        if num_servers > 0:
            ps_root = ("127.0.0.1", find_free_port())
    from dmlc_tpu.resilience import inject as _inject
    from dmlc_tpu.resilience.supervise import (
        GangMember, GangSupervisor, RestartPolicy,
    )
    if isinstance(restart_policy, int):
        restart_policy = RestartPolicy(max_restarts=restart_policy)
    rndv_service = None
    rndv_gang = os.environ.get("DMLC_TPU_RNDV_GANG", "local")
    if rendezvous:
        from dmlc_tpu.rendezvous import RendezvousService
        kw = ({"heartbeat_grace_s": float(heartbeat_grace_s)}
              if heartbeat_grace_s is not None else {})
        rndv_service = RendezvousService(**kw)
    fault_spec = fault_seed = None
    if faults is not None:
        if isinstance(faults, str):
            fault_spec = faults
        else:
            # a FaultPlan's spec() carries clauses only — the plan
            # seed must ride DMLC_TPU_FAULT_SEED or every worker's
            # p= clauses would re-parse onto seed 0, not the armed one
            fault_spec = faults.spec()
            fault_seed = str(faults.seed)

    def _base_env() -> Dict[str, str]:
        e = dict(os.environ)
        if env:
            e.update(env)
        if fault_spec is not None:
            e[_inject.ENV_FAULTS] = fault_spec
        if fault_seed is not None:
            e[_inject.ENV_FAULT_SEED] = fault_seed
        return e

    members: List[GangMember] = []
    for task_id in range(num_workers):
        wenv = _base_env()
        wenv.update(worker_envs(coordinator, num_workers, task_id))
        if trace_dir is not None:
            wenv[ENV_TRACE_DIR] = trace_dir
        if serve_ports is not None:
            wenv[ENV_SERVE_PORT] = str(serve_ports[task_id])
            wenv[ENV_SERVE_PORTS] = ",".join(map(str, serve_ports))
        if flight_dir is not None:
            wenv[ENV_FLIGHT_DIR] = flight_dir
        if history_s is not None:
            wenv[ENV_HISTORY_S] = str(history_s)
        if gang_poll_s is not None and task_id == 0:
            wenv[ENV_GANG_POLL_S] = str(gang_poll_s)
        if profile_hz is not None:
            wenv[ENV_PROFILE_HZ] = str(profile_hz)
        if rndv_service is not None:
            from dmlc_tpu.rendezvous import (
                ENV_RNDV_GANG, ENV_RNDV_PORT, ENV_RNDV_URI,
            )
            wenv[ENV_RNDV_URI] = rndv_service.host
            wenv[ENV_RNDV_PORT] = str(rndv_service.port)
            wenv[ENV_RNDV_GANG] = rndv_gang
        if control:
            wenv[ENV_CONTROL] = "1"
        if scheduler:
            wenv[ENV_SCHED] = (scheduler if isinstance(scheduler, str)
                               else "1")
        if slo:
            wenv[ENV_SLO] = (slo if isinstance(slo, str) else "1")
        if ps_root is not None:
            wenv.update(ps_envs(ps_root[0], ps_root[1], num_workers,
                                num_servers, "worker", task_id))
        members.append(GangMember(f"worker-{task_id}", "worker",
                                  task_id, command, wenv))
    if ps_root is not None:
        roles = [("scheduler", 0)] + [("server", i)
                                      for i in range(num_servers)]
        for role, task_id in roles:
            renv = _base_env()
            renv.update(ps_envs(ps_root[0], ps_root[1], num_workers,
                                num_servers, role, task_id))
            members.append(GangMember(f"{role}-{task_id}", role,
                                      task_id, command, renv))
    # The supervisor owns spawning (a Popen failure mid-loop must not
    # leak the running half of the gang), the gang poll (exited-0-early
    # members keep the gang running; a DIED member kills it on first
    # failure or is restarted under restart_policy), the timeout, and
    # PS-role drain once every worker finished (the pre-resilience loop
    # hung on service roles that wait for work forever).
    try:
        codes = GangSupervisor(
            members, restart_policy=restart_policy,
            timeout=timeout, trace_dir=trace_dir,
            flight_dir=flight_dir,
            rendezvous_addr=(rndv_service.address
                             if rndv_service is not None else None),
            rendezvous_gang=rndv_gang,
            elastic=rndv_service is not None).run()
    finally:
        if rndv_service is not None:
            rndv_service.close()
    if trace_dir is not None:
        merge_gang_traces(trace_dir)
    return codes


def merge_gang_traces(trace_dir: str,
                      out_name: str = "trace-gang.json") -> Optional[str]:
    """Merge the per-worker ``trace-*.json`` files a traced gang left
    in ``trace_dir`` into one Perfetto-loadable timeline. Returns the
    merged path, or None when no worker exported a trace (workers opt
    in via obs.trace.trace_if_env())."""
    import glob
    out_path = os.path.join(trace_dir, out_name)
    paths = sorted(p for p in glob.glob(os.path.join(trace_dir,
                                                     "trace-*.json"))
                   if os.path.abspath(p) != os.path.abspath(out_path))
    if not paths:
        return None
    from dmlc_tpu.obs.export import merge_chrome_files
    merge_chrome_files(paths, out_path)
    return out_path


def rendezvous_envs(rendezvous_addr: Optional[Tuple[str, int]] = None,
                    rendezvous_gang: Optional[str] = None
                    ) -> Dict[str, str]:
    """The rendezvous env contract (``DMLC_TPU_RNDV_URI/PORT/GANG``)
    as a dict ready to merge into worker envs. An explicit
    ``rendezvous_addr=(host, port)`` wins; otherwise the launcher's own
    environment is forwarded (a membership service bound on the submit
    host is reachable from scheduler-launched workers too); empty when
    neither names a service. Shared by launch_ssh and every
    parallel.backends generator so elastic membership is not a
    local/ssh-only feature."""
    from dmlc_tpu.rendezvous import (
        ENV_RNDV_GANG, ENV_RNDV_PORT, ENV_RNDV_URI,
    )
    rndv: Dict[str, str] = {}
    if rendezvous_addr is not None:
        rndv[ENV_RNDV_URI] = str(rendezvous_addr[0])
        rndv[ENV_RNDV_PORT] = str(rendezvous_addr[1])
    elif os.environ.get(ENV_RNDV_URI) and os.environ.get(ENV_RNDV_PORT):
        rndv[ENV_RNDV_URI] = os.environ[ENV_RNDV_URI]
        rndv[ENV_RNDV_PORT] = os.environ[ENV_RNDV_PORT]
    if rndv:
        rndv[ENV_RNDV_GANG] = (rendezvous_gang
                               or os.environ.get(ENV_RNDV_GANG, "local"))
    return rndv


def launch_ssh(hosts: Sequence[str], command: Sequence[str],
               coordinator: str, num_workers: Optional[int] = None,
               dry_run: bool = False,
               rendezvous_addr: Optional[Tuple[str, int]] = None,
               rendezvous_gang: Optional[str] = None) -> List[str]:
    """Generate (and optionally run) per-host ssh commands
    (reference: ssh.py). Returns the command lines.

    The rendezvous env contract rides the command lines: pass
    ``rendezvous_addr=(host, port)`` (and optionally
    ``rendezvous_gang``) to point every worker at an elastic
    membership service, or leave them None and the launcher's own
    ``DMLC_TPU_RNDV_URI/PORT/GANG`` environment (when set) is
    forwarded — a service bound on the submit host is reachable from
    every ssh worker, not just the local gang."""
    n = num_workers or len(hosts)
    rndv = rendezvous_envs(rendezvous_addr, rendezvous_gang)
    lines = []
    for task_id in range(n):
        host = hosts[task_id % len(hosts)]
        envs = dict(worker_envs(coordinator, n, task_id))
        envs.update(rndv)
        env_str = " ".join(f"{k}={shlex.quote(v)}" for k, v in envs.items())
        cmd_str = " ".join(shlex.quote(c) for c in command)
        lines.append(f"ssh -o StrictHostKeyChecking=no {host} "
                     f"'cd {shlex.quote(os.getcwd())} && "
                     f"env {env_str} {cmd_str}'")
    if not dry_run:
        procs = [subprocess.Popen(line, shell=True) for line in lines]
        codes = [p.wait() for p in procs]
        if any(codes):
            raise DMLCError(f"ssh worker failure, exit codes {codes}")
    return lines


# ---------------------------------------------------------------- topology
# Reference: tracker.py get_ring/get_tree/get_link_map (RabitTracker).
# Pure functions; properties tested in tests/test_launch.py.

def get_ring(n: int) -> Dict[int, Tuple[int, int]]:
    """rank -> (prev, next) on a ring (reference: get_ring)."""
    check(n >= 1, "ring needs n >= 1")
    return {r: ((r - 1) % n, (r + 1) % n) for r in range(n)}


def get_tree(n: int) -> Dict[int, int]:
    """rank -> parent (-1 for root) on a binary tree (reference: get_tree)."""
    check(n >= 1, "tree needs n >= 1")
    return {r: ((r - 1) // 2 if r else -1) for r in range(n)}


def get_link_map(n: int) -> Dict[int, List[int]]:
    """rank -> neighbor list combining tree links (reference: get_link_map)."""
    parent = get_tree(n)
    links: Dict[int, List[int]] = {r: [] for r in range(n)}
    for r, p in parent.items():
        if p >= 0:
            links[r].append(p)
            links[p].append(r)
    return links


# ---------------------------------------------------------------- CLI
# Reference: tracker/dmlc-submit + submit.py/opts.py

def main(argv: Optional[Sequence[str]] = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        prog="dmlc-tpu-submit",
        description="Launch distributed workers "
                    "(reference: dmlc-submit; TPU-native rendezvous)")
    ap.add_argument("--cluster", choices=["local", "ssh"], default="local")
    ap.add_argument("-n", "--num-workers", type=int, required=True)
    ap.add_argument("-s", "--num-servers", type=int, default=0,
                    help="PS server processes (reference: dmlc-submit "
                         "--num-servers; spawns scheduler+servers under "
                         "the DMLC_PS_* env contract, local cluster only)")
    ap.add_argument("--host-file", default=None,
                    help="one host per line (ssh cluster)")
    ap.add_argument("--coordinator", default=None,
                    help="host:port of rank-0 coordinator")
    ap.add_argument("command", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)
    check(len(args.command) > 0, "no worker command given")
    cmd = args.command[1:] if args.command[0] == "--" else args.command
    if args.cluster == "local":
        launch_local(args.num_workers, cmd, coordinator=args.coordinator,
                     num_servers=args.num_servers)
    else:
        check(args.num_servers == 0,
              "--num-servers is local-cluster only (ssh PS launch: set "
              "the DMLC_PS_* env per host with ps_envs())")
        check(args.host_file is not None, "--host-file required for ssh")
        with open(args.host_file) as f:
            hosts = [h.strip() for h in f if h.strip()]
        # port chosen by local probe; it must be free on hosts[0] too —
        # pass --coordinator to control it explicitly
        coord = args.coordinator or f"{hosts[0]}:{find_free_port()}"
        launch_ssh(hosts, cmd, coord, num_workers=args.num_workers)
    return 0


if __name__ == "__main__":
    sys.exit(main())
