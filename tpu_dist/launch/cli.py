"""``python -m tpu_dist.launch`` — the torch.distributed.launch CLI (L5).

The reference's second launch mode (/root/reference/README.md:341-343)::

    python -m torch.distributed.launch --nproc_per_node=1 --nnodes=2
        --node_rank=0 --master_addr='...' --master_port=22222 launch_dist.py

This CLI reproduces the exact env contract consumed at
/root/reference/launch_dist.py:45-46 and example_launch.py:17-18: each child
gets ``RANK``, ``LOCAL_RANK``, ``WORLD_SIZE``, ``MASTER_ADDR``,
``MASTER_PORT`` (plus ``LOCAL_WORLD_SIZE``/``NODE_RANK``), then the script
calls ``init_process_group(init_method='env://')``.  ``--pass_local_rank``
additionally appends ``--local_rank=<n>`` to the script's argv (the classic
torch.distributed.launch contract, /root/reference/README.md:341-343; modern
env-only delivery is the default, as torchrun does).

**Control-plane TCPStore** (on by default; ``--no_store`` disables): the
node-0 launcher hosts a :class:`~tpu_dist.dist.store.TCPStore` server (C++
when the toolchain allows, Python otherwise) and passes its address to every
child as ``TPU_DIST_STORE_ADDR`` — the role torch's TCPStore plays behind
``env://`` (/root/reference/mpspawn_dist.py:137-138).  It carries:

- **MASTER_PORT negotiation**: ``--master_port=0`` makes node 0 pick a free
  port; other nodes read it from the store (fixed ``--store_port`` required
  in that multi-node case, since the store is then the only known address);
- **worker liveness**: children check in under ``tpu_dist/alive/<rank>``
  during rendezvous; if the world hasn't fully checked in after
  ``--liveness_warn`` seconds the launcher names the missing ranks on
  stderr instead of letting the rendezvous hang silently;
- **pre-flight + teardown barriers** inside the children's
  ``init_process_group``/``destroy_process_group`` (see
  tpu_dist/dist/rendezvous.py).

TPU deployment note: on a pod slice run ONE launch per host with
``--nproc_per_node=1`` (the process drives all local cores); ``WORLD_SIZE``
then equals nnodes, and the in-process device world is
``dist.get_world_size()`` (cores).  ``--nproc_per_node>1`` is for the CPU
backend (teaching/testing parity with the reference's one-process-per-GPU):
a chip belongs to one process at a time, so ``init_process_group`` refuses
a TPU backend in a child that sees ``LOCAL_WORLD_SIZE > 1``.  The launcher
itself never initialises a JAX backend — it must not hold the chip its
child needs.
"""

from __future__ import annotations

import argparse
import os
import re
import signal
import socket
import subprocess
import sys
import time
from typing import Dict, List, Optional

__all__ = ["main", "build_parser"]


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m tpu_dist.launch",
        description="Launch a script across processes/nodes with the "
                    "RANK/LOCAL_RANK/WORLD_SIZE/MASTER_ADDR/MASTER_PORT "
                    "env contract (torch.distributed.launch parity).")
    p.add_argument("--nproc_per_node", type=int, default=1,
                   help="processes on this node (TPU: 1 per host — a TPU "
                        "backend is refused in children when it is more)")
    p.add_argument("--nnodes", type=int, default=1)
    p.add_argument("--node_rank", type=int, default=0)
    p.add_argument("--master_addr", type=str, default="127.0.0.1")
    p.add_argument("--master_port", type=int, default=29500,
                   help="coordination-service port; 0 = negotiate a free "
                        "port via the store (node 0 picks, others read)")
    p.add_argument("--store_port", type=int, default=0,
                   help="control-plane TCPStore port on node 0 (0 = free "
                        "port single-node, master_port+1 multi-node)")
    p.add_argument("--no_store", action="store_true",
                   help="disable the control-plane store (no port "
                        "negotiation, liveness, or pre-flight)")
    p.add_argument("--store_endpoints", type=str, default=None,
                   metavar="PATH",
                   help="cluster endpoints file (tpu_dist.cluster): the "
                        "launcher and every worker resolve the store "
                        "LEADER from this file and re-resolve it on "
                        "reconnect, so a leader failover (node agents + "
                        "follower replicas, python -m "
                        "tpu_dist.cluster.agent) is transparent. With this "
                        "flag the launcher never hosts the store itself "
                        "unless --store_replica makes node 0 the initial "
                        "leader")
    p.add_argument("--store_replica", action="store_true",
                   help="run the cluster control-plane sidecar inside the "
                        "launcher (needs --store_endpoints): node 0 hosts "
                        "the store with the replication log armed and "
                        "writes the endpoints file; every other node runs "
                        "a follower replica + node agent and can be "
                        "elected leader if node 0's store dies")
    p.add_argument("--liveness_warn", type=float, default=60.0,
                   help="seconds before the node-0 launcher reports ranks "
                        "that have not checked in to the store")
    p.add_argument("--pass_local_rank", action="store_true",
                   help="append --local_rank=<n> to the script args "
                        "(classic torch.distributed.launch argv contract)")
    p.add_argument("--max_restarts", type=int, default=0,
                   help="relaunch the whole world up to N times after a "
                        "worker failure. Multi-node: the launchers agree "
                        "on each restart round through the control-plane "
                        "store (run every node with the SAME "
                        "--max_restarts; needs the store, so not with "
                        "--no_store). Children see TPU_DIST_RESTART_COUNT "
                        "and should resume from their latest checkpoint")
    p.add_argument("--elastic_world", type=str, default=None,
                   metavar="MIN:MAX",
                   help="elastic world-size range (single-node). A worker "
                        "exiting with PREEMPTED_EXIT_CODE (117: pod "
                        "preempted for good; the chaos `shrink` fault) "
                        "re-forms the gang at the surviving rank count "
                        "instead of burning --max_restarts relaunching a "
                        "world that can never fill; GROW_EXIT_CODE (118: "
                        "capacity returned; the chaos `grow` fault) "
                        "re-forms at MAX. World-size changes don't count "
                        "against --max_restarts. Workers resume from "
                        "sharded checkpoints via elastic resharding "
                        "(resilience.TrainState; docs/resilience.md)")
    p.add_argument("--elastic_timeout", type=float, default=120.0,
                   help="seconds to wait for every launcher to join the "
                        "restart agreement before giving up (multi-node "
                        "--max_restarts only)")
    p.add_argument("--restart_backoff", type=float, default=1.0,
                   help="base seconds between restart rounds; doubles each "
                        "round (capped at 30s) with up to 25%% jitter so "
                        "a crash-looping world does not hammer the "
                        "rendezvous")
    p.add_argument("--heartbeat_timeout", type=float, default=0.0,
                   help="seconds of heartbeat silence after which a worker "
                        "counts as lost (RankLostError): the supervisor "
                        "kills the gang and, with --max_restarts, "
                        "relaunches it. Needs the store and workers that "
                        "publish heartbeats (resilience.Heartbeat / "
                        "resilience.TrainState; this flag is exported to "
                        "them as TPU_DIST_HEARTBEAT_TIMEOUT). 0 disables "
                        "the watchdog — a hung rank then waits on the "
                        "coordination-service timeout as before")
    p.add_argument("--sanitize", action="store_true",
                   help="enable the cross-rank collective sanitizer in "
                        "every worker (TPU_DIST_SANITIZE=1): each eager "
                        "host collective cross-checks op/shape/call-site "
                        "agreement through the store before executing, so "
                        "a rank-divergent collective raises a named "
                        "CollectiveMismatchError within "
                        "TPU_DIST_SANITIZE_TIMEOUT instead of hanging "
                        "(tpu_dist/analysis/sanitizer.py)")
    p.add_argument("--coll_timeout", type=float, default=0.0,
                   help="end-to-end collective watchdog in every worker "
                        "(TPU_DIST_COLL_TIMEOUT, seconds): a ring/eager/"
                        "hierarchical host collective that cannot finish "
                        "within the budget — a network partition, a "
                        "wedged peer — raises a named "
                        "CollectiveTimeoutError identifying the stalled "
                        "hop (and the flight-recorder position, when "
                        "armed) instead of waiting out the much longer "
                        "per-frame TPU_DIST_DP_TIMEOUT. 0 disables")
    p.add_argument("--netchaos", type=str, default=None,
                   help="deterministic network fault injection in every "
                        "worker (TPU_DIST_NETCHAOS, tpu_dist/resilience/"
                        "netchaos.py): partition/delay/conn-reset/"
                        "truncate/corrupt/slow-drip faults scoped by "
                        "rank/peer/surface/frame — e.g. "
                        "'corrupt:surface=tcp,rank=1,frame=3'")
    p.add_argument("--flight-recorder", "--flight_recorder",
                   dest="flight_recorder", action="store_true",
                   help="arm the per-rank collective flight recorder in "
                        "every worker (TPU_DIST_OBS=1, tpu_dist.obs): a "
                        "ring buffer of structured events for every host "
                        "collective / p2p / store op / heartbeat, crash-"
                        "dumped to TPU_DIST_OBS_DIR on failure and merged "
                        "into a Chrome trace + hang diagnosis with "
                        "`python -m tpu_dist.obs` (docs/observability.md). "
                        "On a failed round the supervisor prints each "
                        "rank's last known position from the store")
    p.add_argument("--serve", action="store_true",
                   help="start the serving gateway role alongside the "
                        "workers (tpu_dist.serve, docs/serving.md): a "
                        "client-facing proxy on --serve_port that resolves "
                        "the model rank's frontend through the store key "
                        "tpu_dist/serve/backend and SURVIVES worker "
                        "restarts — in-flight requests at a model-rank "
                        "death fail with a named BackendGoneError and new "
                        "requests reach the relaunched rank. Needs the "
                        "control-plane store. Workers run a frontend, e.g. "
                        "examples/serve_lm.py")
    p.add_argument("--serve_port", type=int, default=0,
                   help="gateway's client-facing port (0 = ephemeral; the "
                        "bound address is published to the store under "
                        "tpu_dist/serve/gateway)")
    p.add_argument("--roles", type=str, default=None,
                   metavar="NAME:WORLD[:POLICY],...",
                   help="launch a heterogeneous ROLE GRAPH instead of one "
                        "SPMD world (tpu_dist.roles, docs/roles.md): e.g. "
                        "'learner:1,actor:4:solo' spawns 5 ranks — rank 0 "
                        "the learner, ranks 1-4 actors — each with "
                        "TPU_DIST_ROLE/TPU_DIST_ROLE_RANK set and the "
                        "role map published to the store.  POLICY is the "
                        "per-role supervised-restart policy: 'solo' "
                        "(a dead rank respawns alone, same generation — "
                        "channels resume by name) or 'gang' (default: a "
                        "death fails the round; --max_restarts budgets "
                        "full relaunches).  Roles do not join a "
                        "jax.distributed world — workers call "
                        "tpu_dist.roles.init_role_graph() and talk "
                        "through typed channels / intra-role sub-groups. "
                        "Single-node; needs the control-plane store")
    p.add_argument("--role_script", action="append", default=[],
                   metavar="ROLE=SCRIPT",
                   help="per-role entrypoint override for --roles "
                        "(repeatable): ROLE's ranks run SCRIPT instead of "
                        "the positional script")
    p.add_argument("--solo_restarts", type=int, default=2,
                   help="per-rank respawn budget for 'solo'-policy roles "
                        "within one generation (--roles only)")
    p.add_argument("--verify_graph", "--verify-graph", action="store_true",
                   help="statically model-check the role graph before "
                        "spawning anything (tpu_dist.analysis.protocol, "
                        "docs/analysis.md): channel topology is extracted "
                        "from the script's ChannelSpec literals and checked "
                        "for bounded-queue deadlock cycles (TD101, witness "
                        "schedule printed), claim-safety, restart-policy "
                        "and placement soundness.  Any error-severity "
                        "finding REFUSES the launch with exit 2 "
                        "(--roles only).  Pipeline launches (>= 2 "
                        "stageN roles) run this pre-flight automatically")
    p.add_argument("--no_verify_graph", "--no-verify-graph",
                   action="store_true",
                   help="skip the automatic --verify_graph pre-flight "
                        "that pipeline launches (>= 2 stageN roles) "
                        "otherwise get")
    p.add_argument("--standalone", action="store_true",
                   help="single-node mode with automatic rendezvous "
                        "(torchrun parity): forces --nnodes=1 "
                        "--node_rank=0 and a free master port")
    p.add_argument("--module", "-m", action="store_true",
                   help="treat script as a python module (python -m ...)")
    p.add_argument("script", type=str)
    p.add_argument("script_args", nargs=argparse.REMAINDER)
    return p


def _free_port() -> int:
    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _setup_store(args):
    """Host (node 0) or connect to the control-plane store.

    Returns ``(store, master_port, store_addr)``; ``store`` is None when
    disabled or unavailable (a warning is printed — the store is
    diagnostics + negotiation, not the data path).
    """
    if args.no_store:
        if args.master_port == 0:
            sys.stderr.write("--master_port=0 needs the store for "
                             "negotiation; drop --no_store or pick a port\n")
            return None, None, None
        return None, args.master_port, None

    from ..dist.store import TCPStore

    if getattr(args, "store_endpoints", None) and (
            args.node_rank > 0 or not getattr(args, "store_replica",
                                              False)):
        # Cluster mode: the leader is named by the endpoints file (hosted
        # by node agents, or by node 0's launcher under --store_replica).
        # Every launcher connects as a client; workers inherit the
        # endpoints env and re-resolve on reconnect — that is failover.
        from ..cluster import endpoints as _cep
        os.environ[_cep.ENDPOINTS_ENV] = args.store_endpoints
        deadline = time.monotonic() + 60.0
        addr = _cep.leader_addr(args.store_endpoints)
        while addr is None and time.monotonic() < deadline:
            time.sleep(0.2)
            addr = _cep.leader_addr(args.store_endpoints)
        if addr is None:
            sys.stderr.write(f"no store leader appeared in "
                             f"{args.store_endpoints!r}\n")
            return None, None, None
        try:
            store = TCPStore(addr[0], addr[1], timeout=120.0)
            if args.node_rank == 0:
                master_port = (_free_port() if args.master_port == 0
                               else args.master_port)
                store.set("tpu_dist/master_port", str(master_port))
            elif args.master_port == 0:
                master_port = int(store.get("tpu_dist/master_port"))
            else:
                master_port = args.master_port
            return store, master_port, f"{addr[0]}:{addr[1]}"
        except Exception as e:
            sys.stderr.write(f"store setup failed ({e!r}) against cluster "
                             f"leader {addr[0]}:{addr[1]}\n")
            return None, None, None

    try:
        if args.node_rank == 0:
            port = args.store_port or (args.master_port + 1
                                       if args.nnodes > 1 else 0)
            if args.master_port == 0 and args.nnodes > 1 and not args.store_port:
                sys.stderr.write(
                    "--master_port=0 with --nnodes>1 requires an explicit "
                    "--store_port (the store is then the only known "
                    "address)\n")
                return None, None, None
            store = TCPStore(args.master_addr, port, is_master=True)
            master_port = (_free_port() if args.master_port == 0
                           else args.master_port)
            store.set("tpu_dist/master_port", str(master_port))
            return store, master_port, f"{args.master_addr}:{store.port}"
        else:
            if args.master_port == 0 and not args.store_port:
                sys.stderr.write(
                    "--master_port=0 with --node_rank>0 requires the "
                    "--store_port used on node 0\n")
                return None, None, None
            port = args.store_port or args.master_port + 1
            if args.master_port == 0:
                # the store is the only known address: connect and read the
                # negotiated coordinator port (node 0 may start later, so a
                # generous timeout)
                store = TCPStore(args.master_addr, port, timeout=120.0)
                master_port = int(store.get("tpu_dist/master_port"))
            elif ((args.max_restarts > 0 or args.elastic_world
                   or args.roles) and args.nnodes > 1):
                # multi-node elastic/roles: the restart/world (or gang
                # round) agreement rides the store from EVERY launcher,
                # so connect even though the address is deterministic
                store = TCPStore(args.master_addr, port, timeout=120.0)
                master_port = args.master_port
            else:
                # fixed port: the store address is deterministic, so hand it
                # to the children without blocking this launcher on a
                # connect (node 0 may be slow, absent, or --no_store)
                store, master_port = None, args.master_port
            return store, master_port, f"{args.master_addr}:{port}"
    except Exception as e:
        if args.master_port == 0:
            sys.stderr.write(f"store setup failed ({e!r}); cannot negotiate "
                             f"--master_port=0\n")
            return None, None, None
        sys.stderr.write(f"store setup failed ({e!r}); launching without "
                         f"liveness/pre-flight diagnostics\n")
        return None, args.master_port, None


def _check_liveness(store, world_size: int) -> List[int]:
    """Ranks that have NOT checked in to the store."""
    try:
        return [r for r in range(world_size)
                if not store.check(f"tpu_dist/alive/{r}")]
    except Exception:
        return []


def _spawn_world(args, world_size: int, master_port: int,
                 store_addr: Optional[str], restart_count: int,
                 nproc: Optional[int] = None,
                 base_rank: Optional[int] = None) -> List[subprocess.Popen]:
    """Spawn this node's ranks; on partial failure kill the already-spawned
    ranks (never leave them orphaned in the rendezvous wait) and re-raise.
    ``nproc`` overrides ``--nproc_per_node`` for elastic rounds whose world
    shrank or grew; ``base_rank`` overrides the static
    ``node_rank * nproc_per_node`` span start for rounds where the
    cluster-wide elastic plan reassigned node spans."""
    procs: List[subprocess.Popen] = []
    if nproc is None:
        nproc = args.nproc_per_node
    if base_rank is None:
        base_rank = args.node_rank * args.nproc_per_node
    try:
        for local_rank in range(nproc):
            rank = base_rank + local_rank
            env = dict(os.environ,
                       RANK=str(rank),
                       LOCAL_RANK=str(local_rank),
                       WORLD_SIZE=str(world_size),
                       LOCAL_WORLD_SIZE=str(nproc),
                       NODE_RANK=str(args.node_rank),
                       MASTER_ADDR=args.master_addr,
                       MASTER_PORT=str(master_port),
                       TPU_DIST_RESTART_COUNT=str(restart_count))
            if store_addr is not None:
                env["TPU_DIST_STORE_ADDR"] = store_addr
            if args.heartbeat_timeout > 0:
                env["TPU_DIST_HEARTBEAT_TIMEOUT"] = str(
                    args.heartbeat_timeout)
            env.update(_diagnostic_env(args))
            if getattr(args, "obs_dir", None):
                env["TPU_DIST_OBS"] = "1"
                env["TPU_DIST_OBS_DIR"] = args.obs_dir
            cmd = [sys.executable]
            if args.module:
                cmd += ["-m", args.script]
            else:
                cmd += [args.script]
            cmd += args.script_args
            if args.pass_local_rank:
                cmd += [f"--local_rank={local_rank}"]
            procs.append(subprocess.Popen(cmd, env=env))
    except BaseException:
        # includes KeyboardInterrupt mid-loop: already-spawned children
        # would otherwise sit in the rendezvous pre-flight wait for minutes
        from ..roles.launcher import reap_process
        for p in procs:
            if p.poll() is None:
                reap_process(p)
        raise
    return procs


def _diagnostic_env(args) -> Dict[str, str]:
    """The worker env for the opt-in diagnostic layers (sanitizer,
    collective watchdog, netchaos) — ONE assembly shared by the SPMD
    spawn path and the --roles path, so a new diagnostic knob cannot
    silently apply to only one of them."""
    env: Dict[str, str] = {}
    if getattr(args, "sanitize", False):
        env["TPU_DIST_SANITIZE"] = "1"
    if getattr(args, "coll_timeout", 0) > 0:
        env["TPU_DIST_COLL_TIMEOUT"] = str(args.coll_timeout)
    if getattr(args, "netchaos", None):
        env["TPU_DIST_NETCHAOS"] = args.netchaos
    return env


def _request_obs_dumps(args, procs: List[subprocess.Popen],
                       remaining, rnd: int = 0,
                       base_rank: Optional[int] = None) -> None:
    """Ask still-alive workers to flush their flight recorders (SIGUSR1 ->
    tpu_dist.obs dump handler) before the TERM/KILL teardown, then wait
    (settle-bounded) for the dump files to land.  Armed runs only — a
    worker that never installed the handler would die on USR1, which on
    this (already failed, about to be TERMed) path is harmless but
    pointless.

    The settle wait (shared logic: ``obs.hooks.request_dumps``) exists
    because the TERM that follows can be consumed at the C++ layer
    (jax's preemption notifier owns SIGTERM) and kill the process before
    the Python-level USR1 handler ever ran — the race behind
    intermittently missing per-rank dumps."""
    if getattr(args, "obs_dir", None) is None:
        return
    from ..obs.hooks import request_dumps
    from ..obs.recorder import dump_path

    if base_rank is None:
        base_rank = args.node_rank * args.nproc_per_node
    request_dumps(
        (procs[j], dump_path(args.obs_dir, rnd, base_rank + j))
        for j in remaining)


def _watch_world(args, procs: List[subprocess.Popen], store,
                 world_size: int, rnd: int = 0,
                 base_rank: Optional[int] = None):
    """Monitor one round until every rank exits → ``(exit_code,
    interrupted, rcs)``; ``interrupted`` distinguishes launcher Ctrl-C
    (never restarted) from a worker that happened to exit with code 130,
    and ``rcs`` carries each local rank's exit code so ``--elastic_world``
    can tell preempted ranks (117) and grow requests (118) from crashes.
    Ranks reaped only AFTER this loop's own teardown TERM report ``None``
    — their exit code is a response to the shutdown, not a preemption.

    Fail fast: first non-zero exit kills the rest (mp.spawn-style semantics
    the reference depends on; torch.distributed.launch exits similarly).
    TERM then KILL: jax.distributed installs a SIGTERM handler (preemption
    notifier), so a child in rendezvous/teardown survives terminate() and
    would otherwise linger until the coordination-service heartbeat
    timeout (~100s); escalate to SIGKILL after a grace period.

    Multi-node elastic (``--max_restarts`` with ``--nnodes>1``): a
    launcher that sees a local worker die publishes the round's failure
    key on the store; every launcher polls it (~0.5 s) and tears down its
    own workers on sight, so the whole world stops together — the
    restart *agreement* happens afterwards in :func:`_elastic_agree`.

    ``--elastic_world`` exception to fail-fast: preemptions arrive in
    BATCHES (a spot reclaim takes several pods in one sweep), but this
    loop's first-exit teardown would TERM the not-yet-preempted siblings
    before their own 117s land, miscounting the survivors and re-forming
    at the wrong world.  So when the first failing exit is the elastic
    protocol (PREEMPTED/GROW), teardown waits a short settle window
    (``TPU_DIST_PREEMPT_SETTLE``, default 2 s) collecting further elastic
    exits; any ordinary crash still tears down immediately.
    """
    kill_grace = 15.0
    exit_code = 0
    interrupted = False
    t0 = time.monotonic()
    kill_deadline = None
    if base_rank is None:
        base_rank = args.node_rank * args.nproc_per_node
    liveness_reported = world_size <= 1 or store is None or args.node_rank != 0
    # cross-node failure propagation: armed for the restart agreement AND
    # for multi-node --elastic_world (a preemption on one node must stop
    # the whole world so it can re-form together, restart budget or not)
    elastic = ((args.max_restarts > 0 or args.elastic_world)
               and args.nnodes > 1 and store is not None)
    fail_key = f"tpu_dist/elastic/fail/{rnd}"
    last_remote_check = 0.0
    remote_failed = False
    # Heartbeat watchdog: a rank that is ALIVE but silent (hung collective,
    # stalled host) never trips the exit-code fail-fast below; the monitor
    # converts it into a named RankLostError within the deadline.  Ranks
    # that have not yet published get max(timeout, liveness_warn) of
    # startup grace (workers must import jax before their first beat).
    monitor = None
    hb_poll_every = 0.0
    last_hb_check = 0.0
    if args.heartbeat_timeout > 0 and store is not None:
        from ..resilience.heartbeat import HeartbeatMonitor
        monitor = HeartbeatMonitor(
            store, world_size, timeout=args.heartbeat_timeout,
            generation=rnd,
            startup_grace=max(args.heartbeat_timeout, args.liveness_warn))
        hb_poll_every = min(0.5, args.heartbeat_timeout / 4)
    from ..resilience.chaos import GROW_EXIT_CODE, PREEMPTED_EXIT_CODE
    elastic_rcs = (PREEMPTED_EXIT_CODE, GROW_EXIT_CODE)
    try:
        settle = float(os.environ.get("TPU_DIST_PREEMPT_SETTLE", "2.0"))
    except ValueError:
        settle = 2.0
    teardown_at = None    # when to TERM the still-running ranks
    teardown_done = False
    # exit codes reaped BEFORE the launcher's own teardown TERM went out:
    # a survivor whose --exit-on-preempt handler converts that TERM into
    # a 117 is being shut down by US, not preempted — counting it would
    # collapse the survivor count and veto the shrink it is part of
    pre_teardown_rcs: Dict[int, int] = {}
    try:
        remaining = set(range(len(procs)))
        while remaining:
            if (not liveness_reported
                    and time.monotonic() - t0 > args.liveness_warn):
                liveness_reported = True
                missing = _check_liveness(store, world_size)
                if missing:
                    sys.stderr.write(
                        f"[tpu_dist.launch] after {args.liveness_warn:.0f}s "
                        f"ranks {missing} have not reached rendezvous "
                        f"(checked-in: {world_size - len(missing)}/"
                        f"{world_size}); check --nnodes/--node_rank on "
                        f"every node\n")
            for i in list(remaining):
                rc = procs[i].poll()
                if rc is None:
                    continue
                remaining.discard(i)
                if not teardown_done:
                    pre_teardown_rcs[i] = rc
                if rc == 0 and monitor is not None:
                    # finished ranks are done, not lost — even if they
                    # raced past their terminal exit beat
                    monitor.mark_done(base_rank + i)
                if rc != 0:
                    if exit_code == 0:
                        exit_code = rc
                        if elastic:
                            try:
                                store.set(fail_key,
                                          str(args.node_rank).encode())
                            except Exception:
                                pass
                    if args.elastic_world and rc in elastic_rcs:
                        # batched preemption: let sibling 117/118s land
                        # before tearing down, so the survivor count (and
                        # hence the re-formed world size) is right
                        if teardown_at is None:
                            teardown_at = time.monotonic() + settle
                    else:
                        teardown_at = time.monotonic()
            if (teardown_at is not None and not teardown_done
                    and time.monotonic() >= teardown_at):
                teardown_done = True
                _request_obs_dumps(args, procs, remaining, rnd, base_rank)
                for j in remaining:
                    procs[j].terminate()
                kill_deadline = time.monotonic() + kill_grace
            if (elastic and exit_code == 0 and not remote_failed
                    and time.monotonic() - last_remote_check > 0.5):
                last_remote_check = time.monotonic()
                try:
                    if store.check(fail_key):
                        remote_failed = True
                        sys.stderr.write(
                            "[tpu_dist.launch] another node reported a "
                            "worker failure; stopping local workers\n")
                        # launcher-initiated TERM: a survivor converting
                        # it into a 117 is being shut down by us, not
                        # preempted (see pre_teardown_rcs above)
                        teardown_done = True
                        _request_obs_dumps(args, procs, remaining, rnd, base_rank)
                        for j in remaining:
                            procs[j].terminate()
                        kill_deadline = time.monotonic() + kill_grace
                except Exception:
                    pass
            if (monitor is not None and exit_code == 0 and not remote_failed
                    and time.monotonic() - last_hb_check > hb_poll_every):
                last_hb_check = time.monotonic()
                lost = monitor.poll()
                if lost:
                    monitor = None  # diagnosed; stop polling
                    sys.stderr.write(
                        f"[tpu_dist.launch] RankLostError: {lost[0]}\n")
                    exit_code = 1
                    if elastic:
                        try:
                            store.set(fail_key, str(args.node_rank).encode())
                        except Exception:
                            pass
                    # launcher-initiated TERM (hung rank): survivors'
                    # --exit-on-preempt 117s are OUR shutdown, not a
                    # preemption — without this a hang would silently
                    # shrink the world instead of burning a restart
                    teardown_done = True
                    _request_obs_dumps(args, procs, remaining, rnd, base_rank)
                    for j in remaining:
                        procs[j].terminate()
                    kill_deadline = time.monotonic() + kill_grace
            if (kill_deadline is not None
                    and time.monotonic() > kill_deadline):
                for j in remaining:
                    if procs[j].poll() is None:
                        procs[j].kill()
            if remaining:
                try:
                    procs[next(iter(remaining))].wait(timeout=0.2)
                except subprocess.TimeoutExpired:
                    pass
        if remote_failed and exit_code == 0:
            exit_code = 1  # this node restarts/exits with the group
    except KeyboardInterrupt:
        from ..roles.launcher import reap_process
        for p in procs:
            if p.poll() is None:
                p.send_signal(signal.SIGINT)
        deadline = time.monotonic() + kill_grace
        for p in procs:
            try:
                p.wait(timeout=max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                reap_process(p)
        exit_code = 130
        interrupted = True
    return exit_code, interrupted, [pre_teardown_rcs.get(i)
                                    for i in range(len(procs))]


def _report_obs(args, store, world_size: int, rnd: int) -> None:
    """Per-rank "last known position" table from the flight-recorder tails
    workers posted under ``tpu_dist/g{rnd}/obs/{rank}`` — printed on a
    failed round BEFORE the generation's keyspace is reaped, so the
    operator sees where every rank was without opening a single dump."""
    if args.obs_dir is not None:
        sys.stderr.write(
            f"[tpu_dist.launch] flight-recorder dumps in {args.obs_dir} "
            f"(merge/diagnose: python -m tpu_dist.obs diagnose --dir "
            f"{args.obs_dir})\n")
    if store is None:
        return
    from ..obs.hooks import fetch_tail, render_tail
    rows = [(r, fetch_tail(store, rnd, r)) for r in range(world_size)]
    if all(t is None for _, t in rows):
        return  # recorder disarmed (or no tail made it): stay quiet
    # role annotation from the published role map (tpu_dist.roles): serve
    # ranks read "rank 1 (model-shard[1])", not a bare flat rank — works
    # even when a rank's tail predates its role context (or a SIGKILLed
    # rank posted none), because the map is the launcher-side truth
    labels = {}
    try:
        from ..roles.graph import RoleGraph, map_key
        key = map_key(rnd)
        if store.check(key):
            g = RoleGraph.from_json(store.get(key))
            labels = {r: g.label(r) for r in range(min(world_size,
                                                       g.world))}
    except Exception:
        labels = {}
    sys.stderr.write(f"[tpu_dist.launch] last known positions "
                     f"(generation {rnd}):\n")
    for r, tail in rows:
        if tail is None:
            desc = "no obs tail posted"
        else:
            try:
                desc = render_tail(tail)
            except Exception:
                desc = str(tail)
        who = f"rank {r} ({labels[r]})" if r in labels else f"rank {r}"
        sys.stderr.write(f"  {who}: {desc}\n")


def _report_reshard_plan(store, new_world: int) -> None:
    """Print the elastic resharding plan summary next to the restart log
    (best-effort diagnostics): the workers published their checkpoint root
    under ``tpu_dist/elastic/ckpt_root`` (resilience.TrainState); from the
    newest locally-resumable step's manifest the supervisor derives the
    exact old-world → ``new_world`` fragment redistribution the re-formed
    gang is about to run, before it starts fetching."""
    if store is None:
        return
    try:
        if not store.check("tpu_dist/elastic/ckpt_root"):
            return
        root = store.get("tpu_dist/elastic/ckpt_root").decode()
        from ..resilience import reshard
        vis = reshard.local_visibility(root)
        steps = reshard.resumable_steps([vis])
        if not steps:
            return
        step = max(steps)
        manifest = None
        for o in sorted(vis["shards"]):
            if vis["shards"][o].get(step) == steps[step]:
                manifest = reshard.load_manifest(root, step, o)
                if manifest is not None:
                    break
        if manifest is None:
            return
        summary = reshard.plan_summary(manifest, new_world)
        sys.stderr.write("".join(f"[tpu_dist.launch] {line}\n"
                                 for line in summary.splitlines()))
    except Exception:
        pass  # a summary must never block the restart


def _elastic_new_world(elastic_range, cur_world: int,
                       rcs: List[Optional[int]]) -> Optional[int]:
    """The world size the next round should re-form at, or None when this
    failed round is NOT an elastic world change (ordinary crash — the
    normal restart budget applies).

    A worker exiting :data:`~tpu_dist.resilience.chaos.PREEMPTED_EXIT_CODE`
    (117) announced its rank is gone for good: re-form at the surviving
    rank count (clamped to MIN; below MIN there is no legal world, so the
    round falls back to a budgeted full-world restart and a later retry).
    :data:`~tpu_dist.resilience.chaos.GROW_EXIT_CODE` (118) announced
    capacity is back: re-form at MAX — but a simultaneous preemption wins
    (the grow request came from a world that no longer exists)."""
    if elastic_range is None:
        return None
    from ..resilience.chaos import GROW_EXIT_CODE, PREEMPTED_EXIT_CODE
    lo, hi = elastic_range
    preempted = sum(1 for rc in rcs if rc == PREEMPTED_EXIT_CODE)
    if preempted:
        surviving = cur_world - preempted
        if surviving < lo:
            sys.stderr.write(
                f"[tpu_dist.launch] {preempted} rank(s) preempted but "
                f"{surviving} survivors is below --elastic_world MIN "
                f"{lo}; retrying at the full world size\n")
            return None
        return surviving if surviving != cur_world else None
    if any(rc == GROW_EXIT_CODE for rc in rcs):
        # already at MAX: a redundant grow request (a production capacity
        # watcher racing the regrow, or firing twice) is a free same-world
        # relaunch, not a crash — the fall-through would kill the job at
        # --max_restarts=0
        return hi if hi != cur_world else cur_world
    return None


def _reset_round_state(store,
                       finished_round: Optional[int] = None) -> None:
    """Reset last round's control-plane state before a restart: liveness
    marks AND the teardown-barrier arrival counter — a partial teardown
    (one rank crashed mid-round) leaves the counter off-generation, which
    would make the next round's first teardown caller sail through the
    barrier early.  The finished round's heartbeat keys go too (they are
    generation-scoped, so this is pure GC — a stale publisher cannot
    refresh the next round's keys either way)."""
    try:
        # one server-side sweep instead of world_size delete_key
        # round-trips (DELETE_PREFIX, wire op 8)
        store.delete_prefix("tpu_dist/alive/")
    except Exception:
        pass
    if finished_round is not None:
        try:
            store.delete_prefix(f"tpu_dist/hb/{finished_round}/")
        except Exception:
            pass
        # reap the crashed generation's ENTIRE keyspace (in-flight
        # collective payloads, dp addresses, p2p frames, sanitizer
        # signatures): one server-side DELETE_PREFIX sweep.  Safe because
        # every worker of generation N scopes its payload keys under
        # tpu_dist/g{N}/ and the gang is already torn down when this runs;
        # without it each failed round leaked up to one step's payloads
        # (the PR 2 KNOWN LIMIT this closes).
        try:
            store.delete_prefix(f"tpu_dist/g{finished_round}/")
        except Exception:
            pass
    try:
        store.delete_key("__barrier__/teardown")
    except Exception:
        pass


def _publish_generation(store, rnd: int) -> None:
    """Fence out stragglers from previous incarnations: children compare
    their TPU_DIST_RESTART_COUNT against this key at rendezvous pre-flight
    (tpu_dist/dist/rendezvous.py)."""
    try:
        store.set("tpu_dist/generation", str(rnd))
    except Exception:
        pass


def _restart_backoff(args, restarts: int) -> None:
    """Exponential backoff + jitter before a relaunch round: restart storms
    against a struggling host/store help nobody, and the jitter de-phases
    multi-node launchers racing to re-rendezvous."""
    import random

    if args.restart_backoff <= 0:
        return
    delay = (min(args.restart_backoff * 2 ** (restarts - 1), 30.0)
             * (1.0 + 0.25 * random.random()))
    sys.stderr.write(f"[tpu_dist.launch] backing off {delay:.1f}s before "
                     f"restart {restarts}\n")
    time.sleep(delay)


def _elastic_exit_sync(args, store, rnd: int) -> None:
    """Final ack before launchers exit the elastic protocol: node 0 hosts
    the store, so it must not return (and tear the server down) while a
    peer is still polling the agreement counters — the peer would see a
    ConnectionError instead of its own clean verdict."""
    try:
        key = f"tpu_dist/elastic/exit/{rnd}"
        store.add(key, 1)
        if args.node_rank == 0:
            store.wait_value_ge(key, args.nnodes,
                                timeout=min(15.0, args.elastic_timeout))
    except Exception:
        pass  # best effort: worst case is the peer's noisier error path


def _elastic_agree(args, store, rnd: int, local_rc: int,
                   negotiated_port: bool, master_port: int):
    """Cross-launcher end-of-round agreement (multi-node elastic).

    Returns ``("done", rc)``, ``("restart", new_master_port)``, or
    ``("giveup", rc)``.  Protocol, all keys round-scoped so no cleanup
    races between rounds (every launcher must run with the same
    ``--max_restarts``):

    1. every launcher adds itself to ``done/{rnd}`` once its local
       workers have exited (success or failure alike);
    2. waits until all ``--nnodes`` have arrived (bounded by
       ``--elastic_timeout`` — a vanished peer machine must not hang the
       group forever);
    3. outcome = failure iff ``fail/{rnd}`` was published by anyone;
    4. on restart: node 0 re-picks the coordinator port when it was
       store-negotiated, resets liveness/teardown keys, then publishes
       ``go/{rnd}`` — the other launchers respawn only after reading it
       (workers must not race the control-plane reset).
    """
    prefix = "tpu_dist/elastic"
    nnodes = args.nnodes
    try:
        if local_rc != 0:
            # re-publish before arriving at the done barrier: the watch
            # loop's best-effort publish may have been swallowed by a
            # transient store error, and peers must not read this round
            # as a success
            store.set(f"{prefix}/fail/{rnd}", str(args.node_rank).encode())
        store.add(f"{prefix}/done/{rnd}", 1)
        store.wait_value_ge(f"{prefix}/done/{rnd}", nnodes,
                            timeout=args.elastic_timeout)
        # this node's own verdict counts even if no publish ever landed
        failed = local_rc != 0 or store.check(f"{prefix}/fail/{rnd}")
    except Exception as e:
        sys.stderr.write(f"[tpu_dist.launch] elastic agreement failed "
                         f"({e!r}); giving up\n")
        return ("giveup", local_rc or 1)
    if not failed:
        _elastic_exit_sync(args, store, rnd)
        return ("done", 0)
    if rnd >= args.max_restarts:
        _elastic_exit_sync(args, store, rnd)
        return ("giveup", local_rc or 1)
    rc_port = master_port
    try:
        if args.node_rank == 0:
            if negotiated_port:
                rc_port = _free_port()
            _reset_round_state(store, finished_round=rnd)
            store.set(f"{prefix}/go/{rnd}", str(rc_port).encode())
        else:
            store.wait([f"{prefix}/go/{rnd}"],
                       timeout=args.elastic_timeout)
            rc_port = int(store.get(f"{prefix}/go/{rnd}"))
    except Exception as e:
        sys.stderr.write(f"[tpu_dist.launch] elastic restart handshake "
                         f"failed ({e!r}); giving up\n")
        return ("giveup", local_rc or 1)
    return ("restart", rc_port)


def _cluster_agree(args, store, rnd: int, local_rc: int,
                   rcs: List[Optional[int]], cur_nproc: int,
                   restarts: int, negotiated_port: bool, master_port: int,
                   elastic_range):
    """Cross-launcher end-of-round agreement, world-change aware.

    The multi-node generalization of :func:`_elastic_agree` (same
    round-scoped done/fail/go keys, same budgeted-restart semantics) plus
    the cluster elastic decision: before the done barrier every launcher
    publishes its node's round counts (preempted 117s, grow 118s, ranks
    run), and after it every launcher independently evaluates the SAME
    pure plan (:func:`tpu_dist.cluster.membership.elastic_plan`) over the
    same store-agreed counts + membership records — so all launchers agree
    which node's ranks drop and what base rank each surviving span starts
    at, with no coordinator and no extra votes.

    Returns ``("done", 0)``, ``("giveup", rc)``,
    ``("restart", new_master_port)`` or
    ``("reform", (port, world, base_rank, nproc))`` — reform does NOT
    charge the restart budget.
    """
    import json as _json

    from ..cluster import membership as _cm
    from ..resilience.chaos import GROW_EXIT_CODE, PREEMPTED_EXIT_CODE

    prefix = "tpu_dist/elastic"
    nnodes = args.nnodes
    try:
        if elastic_range is not None:
            _cm.publish_elastic_counts(
                store, rnd, args.node_rank, nproc=cur_nproc,
                full_nproc=args.nproc_per_node,
                preempted=sum(1 for rc in rcs
                              if rc == PREEMPTED_EXIT_CODE),
                grow=any(rc == GROW_EXIT_CODE for rc in rcs))
        if local_rc != 0:
            store.set(f"{prefix}/fail/{rnd}", str(args.node_rank).encode())
        store.add(f"{prefix}/done/{rnd}", 1)
        # an idle node (0 ranks this round) exits its watch loop instantly
        # and must wait out the whole training phase here — unbounded,
        # server-side blocking, not the agreement timeout
        store.wait_value_ge(f"{prefix}/done/{rnd}", nnodes,
                            timeout=(None if cur_nproc == 0
                                     else args.elastic_timeout))
        failed = local_rc != 0 or store.check(f"{prefix}/fail/{rnd}")
        plan = None
        if failed and elastic_range is not None:
            counts = _cm.gather_elastic_counts(store, rnd, nnodes,
                                               timeout=args.elastic_timeout)
            records = _cm.read_nodes(store, nnodes)
            plan = _cm.elastic_plan(counts, records, elastic_range[0],
                                    elastic_range[1])
    except Exception as e:
        sys.stderr.write(f"[tpu_dist.launch] cluster agreement failed "
                         f"({e!r}); giving up\n")
        return ("giveup", local_rc or 1)
    if not failed:
        _elastic_exit_sync(args, store, rnd)
        return ("done", 0)
    if plan is None and restarts >= args.max_restarts:
        _elastic_exit_sync(args, store, rnd)
        return ("giveup", local_rc or 1)
    rc_port = master_port
    try:
        if args.node_rank == 0:
            if negotiated_port:
                rc_port = _free_port()
            _reset_round_state(store, finished_round=rnd)
            store.set(f"{prefix}/go/{rnd}",
                      _json.dumps({"port": rc_port,
                                   "plan": ({str(n): list(v)
                                             for n, v in plan.items()}
                                            if plan else None)}).encode())
        else:
            store.wait([f"{prefix}/go/{rnd}"],
                       timeout=(None if cur_nproc == 0
                                else args.elastic_timeout))
            go = _json.loads(store.get(f"{prefix}/go/{rnd}").decode())
            rc_port = int(go["port"])
            remote_plan = go.get("plan")
            # every launcher computed the same plan from the same inputs;
            # trusting node 0's published copy just removes any chance of
            # a read racing a late count re-publish
            plan = ({int(n): tuple(v) for n, v in remote_plan.items()}
                    if remote_plan else None)
    except Exception as e:
        sys.stderr.write(f"[tpu_dist.launch] cluster restart handshake "
                         f"failed ({e!r}); giving up\n")
        return ("giveup", local_rc or 1)
    if plan is not None:
        base, nproc = plan.get(args.node_rank, (0, 0))
        world = sum(np for _, np in plan.values())
        return ("reform", (rc_port, world, base, nproc))
    return ("restart", rc_port)


def _verify_role_graph(args) -> int:
    """``--verify_graph`` pre-flight: statically model-check the role
    graph + channel topology (tpu_dist.analysis.protocol) BEFORE spawning
    anything, refusing a provably-hazardous graph.  A TD101 deadlock
    finding prints its witness schedule — the concrete put/get
    interleaving that wedges every role in the cycle."""
    from ..analysis.protocol import build_graph, verify_graph

    src = args.script if (args.script and not args.module
                          and os.path.exists(args.script)) else None
    label = src or "<--roles spec>"
    graph = None
    findings: list = []
    notes: list = []
    if src:
        # a script exporting a module-level build_graph() (the
        # examples/pipeline_train.py idiom) hands us the REAL graph —
        # builder-constructed ChannelSpecs that literal extraction
        # can't see.  Anything else falls back to extraction.
        try:
            graph = build_graph(graph_target=f"{src}:build_graph",
                                path=label)[0]
            notes.append(f"graph from {src}:build_graph()")
        except Exception:
            graph = None
    if graph is None:
        graph, findings, notes = build_graph(roles_spec=args.roles,
                                             script=src, path=label)
    if graph is not None:
        findings = list(findings) + verify_graph(graph, nnodes=args.nnodes,
                                                 path=label)
    for note in notes:
        sys.stderr.write(f"--verify_graph: note: {note}\n")
    for f in findings:
        sys.stderr.write(f.render() + "\n")
    errors = [f for f in findings if f.severity == "error"
              and not f.suppressed]
    if errors:
        sys.stderr.write(
            f"--verify_graph: refusing to launch — {len(errors)} "
            f"error-severity protocol finding(s) above (run "
            f"'python -m tpu_dist.analysis graph' for the full report)\n")
        return 2
    return 0


def _run_role_graph(args) -> int:
    """``--roles``: launch a heterogeneous role graph (tpu_dist.roles)
    instead of one SPMD world.  The graph supervisor
    (:func:`tpu_dist.roles.spawn_graph`) owns the store, the role-map
    publication, and per-role restart routing; this wrapper only
    validates the CLI surface and assembles the worker env/argv."""
    from ..roles import RoleGraphError, parse_roles_spec, spawn_graph

    if args.no_store:
        sys.stderr.write("--roles needs the control-plane store (role map, "
                         "channels, liveness); drop --no_store\n")
        return 2
    if args.elastic_world:
        sys.stderr.write("--roles and --elastic_world are mutually "
                         "exclusive: per-role restart policy IS the "
                         "elastic story for role graphs\n")
        return 2
    if args.max_restarts < 0 or args.solo_restarts < 0:
        sys.stderr.write("restart budgets must be >= 0\n")
        return 2
    try:
        graph = parse_roles_spec(args.roles)
    except RoleGraphError as e:
        sys.stderr.write(f"--roles: {e}\n")
        return 2
    # pipeline launches (>= 2 stageN roles) get the pre-flight
    # automatically: a mis-depthed act/grad ring deadlocks every stage,
    # so refusing before spawn with a witness beats hanging after
    pipelined = sum(1 for r in graph.roles
                    if re.fullmatch(r"stage\d+", r.name)) >= 2
    if args.verify_graph or (pipelined and not args.no_verify_graph):
        rc = _verify_role_graph(args)
        if rc:
            return rc
    if args.nnodes > 1:
        # multi-node role placement: @node pins decide which launcher
        # supervises which span (unpinned roles are node 0's); every
        # launcher validates the same pins against the same cluster size
        from ..cluster.membership import validate_placement
        try:
            validate_placement(graph, args.nnodes)
        except ValueError as e:
            sys.stderr.write(f"--roles: {e}\n")
            return 2
    argv = [sys.executable]
    argv += ["-m", args.script] if args.module else [args.script]
    argv += args.script_args
    role_argv = {}
    for spec in args.role_script:
        name, _, script = spec.partition("=")
        if not script:
            sys.stderr.write(f"--role_script must be ROLE=SCRIPT, got "
                             f"{spec!r}\n")
            return 2
        try:
            graph.role(name)
        except RoleGraphError as e:
            sys.stderr.write(f"--role_script: {e}\n")
            return 2
        role_argv[name] = [sys.executable, script] + list(args.script_args)
    extra_env = _diagnostic_env(args)
    store = None
    gateway_proc = None
    store_addr = None
    if args.nnodes > 1:
        # shared store across launchers: node 0 hosts (or the cluster
        # leader named by --store_endpoints serves), everyone connects —
        # the gang round agreement rides it from every node
        if args.store_replica:
            os.environ["TPU_DIST_STORE_REPLICATE"] = "1"
        store, _mp, store_addr = _setup_store(args)
        if store is None or store_addr is None:
            sys.stderr.write("--roles with --nnodes>1 needs a working "
                             "control-plane store; fix the store setup "
                             "error above\n")
            return 2
        if args.store_replica and args.node_rank == 0:
            from ..cluster import endpoints as _cep
            _cep.write_endpoints(args.store_endpoints, store_addr, 0)
            os.environ[_cep.ENDPOINTS_ENV] = args.store_endpoints
    if args.serve and args.node_rank == 0:
        # the serving gateway rides OUTSIDE the graph's restart loop —
        # like the SPMD path, its whole point is surviving gang rounds
        # (it re-resolves the backend registry after each restart).  Host
        # the store here so the gateway and spawn_graph share it (multi-
        # node launches already hold the shared store from above).
        if store is None:
            from ..dist.store import TCPStore
            try:
                store = TCPStore(args.master_addr, args.store_port,
                                 is_master=True)
            except Exception as e:
                sys.stderr.write(f"--roles --serve: store setup failed "
                                 f"({e})\n")
                return 2
            store_addr = f"{args.master_addr}:{store.port}"
        gw_env = dict(os.environ, TPU_DIST_STORE_ADDR=store_addr)
        gateway_proc = subprocess.Popen(
            [sys.executable, "-m", "tpu_dist.serve", "gateway",
             "--port", str(args.serve_port)], env=gw_env)
    try:
        return spawn_graph(graph, argv, role_argv or None,
                           max_restarts=args.max_restarts,
                           solo_restarts=args.solo_restarts,
                           heartbeat_timeout=args.heartbeat_timeout,
                           restart_backoff=args.restart_backoff,
                           master_addr=args.master_addr,
                           store_port=args.store_port,
                           store=store, store_addr=store_addr,
                           extra_env=extra_env, obs_dir=args.obs_dir,
                           node_id=args.node_rank, nnodes=args.nnodes)
    finally:
        if gateway_proc is not None and gateway_proc.poll() is None:
            gateway_proc.terminate()
            try:
                gateway_proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                from ..roles.launcher import reap_process
                reap_process(gateway_proc)
        if store is not None:
            try:
                store.close()
            except Exception:
                pass


def main(argv: Optional[List[str]] = None) -> int:
    args = build_parser().parse_args(argv)
    if args.standalone:
        overridden = [f for f, default in (("--nnodes", 1),
                                           ("--node_rank", 0),
                                           ("--master_port", 29500))
                      if getattr(args, f[2:]) != default]
        if overridden:
            sys.stderr.write(
                f"--standalone overrides {', '.join(overridden)} "
                f"(single-node, auto rendezvous port)\n")
        args.nnodes, args.node_rank = 1, 0
        # torchrun's --standalone needs no store: pick the port directly
        # rather than via store negotiation (which --no_store disables)
        args.master_port = _free_port() if args.no_store else 0
    if args.node_rank >= args.nnodes or args.node_rank < 0:
        sys.stderr.write(f"--node_rank {args.node_rank} out of range for "
                         f"--nnodes {args.nnodes}\n")
        return 2
    if args.max_restarts < 0:
        sys.stderr.write(f"--max_restarts must be >= 0\n")
        return 2
    if args.max_restarts > 0 and args.nnodes > 1 and args.no_store:
        # the cross-launcher restart agreement rides the store
        sys.stderr.write("--max_restarts with --nnodes>1 needs the "
                         "control-plane store; drop --no_store\n")
        return 2
    if args.store_replica and not args.store_endpoints:
        sys.stderr.write("--store_replica needs --store_endpoints (the "
                         "shared file clients re-resolve the leader "
                         "from)\n")
        return 2
    if (args.store_endpoints or args.store_replica) and args.no_store:
        sys.stderr.write("--store_endpoints/--store_replica need the "
                         "control-plane store; drop --no_store\n")
        return 2
    world_size = args.nproc_per_node * args.nnodes
    elastic_range = None
    if args.elastic_world:
        try:
            lo, hi = (int(v) for v in args.elastic_world.split(":"))
        except ValueError:
            sys.stderr.write(f"--elastic_world must be MIN:MAX, got "
                             f"{args.elastic_world!r}\n")
            return 2
        if not 1 <= lo <= hi:
            sys.stderr.write(f"--elastic_world needs 1 <= MIN <= MAX, got "
                             f"{lo}:{hi}\n")
            return 2
        if args.nnodes > 1 and hi != args.nproc_per_node * args.nnodes:
            # the cluster grow decision restores each node to its
            # configured capacity, so MAX must be the full static world —
            # anything else would silently cap growth below what the
            # flags promise
            sys.stderr.write(f"--elastic_world MAX must equal "
                             f"nproc_per_node*nnodes "
                             f"({args.nproc_per_node * args.nnodes}) with "
                             f"--nnodes>1, got {hi}\n")
            return 2
        if args.no_store:
            # generation fencing + the reshard visibility exchange ride
            # the store; an elastic world without it could let a stale
            # rank from the pre-shrink incarnation join the new gang
            sys.stderr.write("--elastic_world needs the control-plane "
                             "store; drop --no_store\n")
            return 2
        if not lo <= world_size <= hi:
            sys.stderr.write(f"--nproc_per_node={args.nproc_per_node} is "
                             f"outside --elastic_world={lo}:{hi}\n")
            return 2
        elastic_range = (lo, hi)
    # flight-recorder wiring: --flight-recorder (or an already-armed env)
    # resolves ONE dump dir shared by supervisor messages and every worker.
    # The env test MUST be the recorder's own parser: a bare truthiness
    # check would invert an explicit TPU_DIST_OBS=0 into forced arming.
    from ..obs.recorder import enabled as _obs_enabled
    args.obs_dir = None
    if args.flight_recorder or _obs_enabled():
        args.obs_dir = (os.environ.get("TPU_DIST_OBS_DIR")
                        or os.path.join(os.getcwd(), "tpu_dist_obs"))

    if args.roles:
        return _run_role_graph(args)

    if args.store_replica:
        # replication must be armed BEFORE the store is hosted (node 0's
        # server owns the mutation log) and forces the Python wire path
        # everywhere in this process tree
        os.environ["TPU_DIST_STORE_REPLICATE"] = "1"
    store, master_port, store_addr = _setup_store(args)
    if master_port is None:
        return 2
    negotiated_port = args.master_port == 0
    cluster_agent = None
    cluster_follower = None
    if args.store_replica and store is not None:
        from ..cluster import NodeAgent, StoreFollower
        from ..cluster import endpoints as _cep
        try:
            if args.node_rank == 0:
                # this launcher's store IS the initial leader
                _cep.write_endpoints(args.store_endpoints, store_addr, 0)
                os.environ[_cep.ENDPOINTS_ENV] = args.store_endpoints
                cluster_agent = NodeAgent(0, args.store_endpoints,
                                          nproc=args.nproc_per_node)
                cluster_agent.is_leader.set()
                cluster_agent.start()
            else:
                addr = _cep.leader_addr(args.store_endpoints)
                cluster_follower = StoreFollower(addr[0], addr[1]).start()
                cluster_agent = NodeAgent(args.node_rank,
                                          args.store_endpoints,
                                          follower=cluster_follower,
                                          nproc=args.nproc_per_node)
                cluster_agent.start()
        except Exception as e:
            sys.stderr.write(f"--store_replica: cluster sidecar setup "
                             f"failed ({e!r})\n")
            return 2
    elif (args.nnodes > 1 and store is not None
          and (elastic_range or args.max_restarts > 0)):
        # membership record for the cluster elastic plan (host-fingerprint
        # node ordering) even without the replication sidecar
        try:
            from ..cluster.membership import register_node
            register_node(store, args.node_rank, args.nproc_per_node)
        except Exception:
            pass

    multi_node = (args.nnodes > 1
                  and (args.max_restarts > 0 or elastic_range is not None))
    if multi_node and store is None:
        # store setup failed above (warning already printed): without it
        # there is no cross-node failure propagation or restart agreement
        # — refuse rather than silently run non-elastic and then exit 1
        # from a doomed agreement
        sys.stderr.write("--max_restarts/--elastic_world with --nnodes>1 "
                         "needs a working control-plane store; fix the "
                         "store setup error above or drop the flag\n")
        return 2
    # --serve: the gateway role is spawned ONCE, outside the restart loop
    # — its whole point is surviving worker relaunches (it re-resolves the
    # backend address from the store after each restart)
    gateway_proc = None
    if args.serve:
        if store_addr is None:
            sys.stderr.write("--serve needs the control-plane store "
                             "(drop --no_store / fix the store error "
                             "above)\n")
            return 2
        if args.node_rank == 0:
            gw_env = dict(os.environ, TPU_DIST_STORE_ADDR=store_addr)
            gateway_proc = subprocess.Popen(
                [sys.executable, "-m", "tpu_dist.serve", "gateway",
                 "--port", str(args.serve_port)], env=gw_env)

    restarts = 0   # failure budget, compared against --max_restarts
    rnd = 0        # generation: EVERY relaunch (failure OR elastic world
    #                change) advances it, so a re-formed gang can never
    #                collide with a stale rank's store keyspace — which is
    #                why world-size changes can ride outside the restart
    #                budget in the first place
    cur_world = world_size
    cur_nproc = args.nproc_per_node
    base_rank = args.node_rank * args.nproc_per_node
    try:
        while True:
            if store is not None and args.node_rank == 0:
                _publish_generation(store, rnd)
            procs = _spawn_world(args, cur_world, master_port, store_addr,
                                 rnd, nproc=cur_nproc, base_rank=base_rank)
            exit_code, interrupted, rcs = _watch_world(args, procs, store,
                                                       cur_world, rnd=rnd,
                                                       base_rank=base_rank)
            if interrupted:
                return exit_code
            if exit_code != 0 and args.node_rank == 0:
                # before any reaping: the tails live under the failed
                # generation's keyspace
                _report_obs(args, store, cur_world, rnd)
            if multi_node:
                # group decision: even a node whose workers all exited 0
                # (or an idle node running none this round) must wait — a
                # peer's failure restarts everyone, a peer's preemption or
                # grow re-forms the world for everyone
                verdict, val = _cluster_agree(args, store, rnd, exit_code,
                                              rcs, cur_nproc, restarts,
                                              negotiated_port, master_port,
                                              elastic_range)
                if verdict == "done":
                    return 0
                if verdict == "giveup":
                    return val
                if verdict == "reform":
                    # cluster elastic re-form: world size and/or rank
                    # placement changed (the plan may drop THIS node to 0
                    # ranks — it idles in the agreement until a grow).
                    # Not a failure restart: budget untouched, generation
                    # still advances (same contract as single-node).
                    master_port, new_world, base_rank, cur_nproc = val
                    rnd += 1
                    sys.stderr.write(
                        f"[tpu_dist.launch] cluster elastic re-form: "
                        f"world {cur_world} -> {new_world}, node "
                        f"{args.node_rank} runs {cur_nproc} rank(s) from "
                        f"base {base_rank} (generation {rnd}; restart "
                        f"budget untouched at "
                        f"{restarts}/{args.max_restarts})\n")
                    if args.node_rank == 0:
                        _report_reshard_plan(store, new_world)
                    cur_world = new_world
                    _restart_backoff(args, 1)
                    continue
                master_port = val
                restarts += 1
                rnd += 1
                sys.stderr.write(
                    f"[tpu_dist.launch] world failed; agreed restart "
                    f"{restarts}/{args.max_restarts} across "
                    f"{args.nnodes} nodes — relaunching"
                    + (f" (obs dumps: {args.obs_dir})"
                       if args.obs_dir else "") + "\n")
                _restart_backoff(args, restarts)
                continue
            new_world = (_elastic_new_world(elastic_range, cur_world, rcs)
                         if exit_code != 0 else None)
            if new_world is not None:
                # elastic re-form: preempted ranks are gone FOR GOOD (117)
                # or capacity returned (118) — change the world size
                # instead of burning --max_restarts relaunching a world
                # that can never fill.  Not a failure restart, so the
                # budget stays untouched; the generation still advances.
                # other nonzero rcs reaped in the same round are treated
                # as COLLATERAL fallout of the dying gang, not charged:
                # a preempted peer routinely takes survivors down with
                # it (PeerGoneError, the jax coordination service's
                # "another task died" abort) before the settle-window
                # teardown lands, and those deaths are indistinguishable
                # from independent crashes
                rnd += 1
                sys.stderr.write(
                    f"[tpu_dist.launch] elastic world change: "
                    f"{cur_world} -> {new_world} (generation {rnd}; "
                    f"restart budget untouched at "
                    f"{restarts}/{args.max_restarts}) — re-forming\n")
                if args.node_rank == 0:
                    _report_reshard_plan(store, new_world)
                cur_world = new_world
                cur_nproc = new_world  # single-node: ranks == local ranks
                if store is not None:
                    _reset_round_state(store, finished_round=rnd - 1)
                _restart_backoff(args, 1)
                if negotiated_port:
                    master_port = _free_port()
                continue
            if exit_code == 0 or restarts >= args.max_restarts:
                return exit_code
            restarts += 1
            rnd += 1
            sys.stderr.write(
                f"[tpu_dist.launch] worker failed (rc={exit_code}); "
                f"restart {restarts}/{args.max_restarts} — relaunching "
                f"the world"
                + (f" (obs dumps: {args.obs_dir})"
                   if args.obs_dir else "") + "\n")
            if store is not None:
                _reset_round_state(store, finished_round=rnd - 1)
            _restart_backoff(args, restarts)
            if negotiated_port:
                # the old coordinator socket may still be in TIME_WAIT;
                # single-node restarts hand children the fresh port via
                # env — no store re-publication needed
                master_port = _free_port()
    finally:
        if cluster_agent is not None:
            try:
                cluster_agent.stop()
            except Exception:
                pass
        if cluster_follower is not None:
            try:
                cluster_follower.stop()
            except Exception:
                pass
        if gateway_proc is not None and gateway_proc.poll() is None:
            gateway_proc.terminate()
            try:
                gateway_proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                from ..roles.launcher import reap_process
                reap_process(gateway_proc)
        if store is not None:
            try:
                store.close()
            except Exception:
                pass
