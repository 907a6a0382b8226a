"""Serving worker — the model-rank half of ``python -m tpu_dist.launch
--serve`` (docs/serving.md).

Builds a :class:`~tpu_dist.models.TransformerLM`, wraps it in the
continuous-batching :class:`~tpu_dist.serve.SlotEngine` +
:class:`~tpu_dist.serve.Scheduler`, and listens with a
:class:`~tpu_dist.serve.Frontend` whose address is registered in the
control-plane store's backend registry so the launcher-spawned gateway
finds it — including ACROSS supervised restarts, which is what makes the
chaos story work: SIGKILL this process under load, the supervisor
relaunches it, the fresh address lands under the same backend name, and
the gateway's next submit reaches the new incarnation::

    python -m tpu_dist.launch --standalone --max_restarts=3 --serve \\
        examples/serve_lm.py --tiny

Three multi-rank shapes (docs/serving.md#multi-rank):

- ``--backend-name NAME`` — independent **replicas**: run several
  launchers (or workers) against one store, each registering a distinct
  name; the gateway load-balances across them (least outstanding
  requests) and fails over between them.
- ``--sharded`` — **tensor-parallel decode**: every rank the launcher
  spawned is one shard of a ``model-shard`` group
  (``tpu_dist.serve.sharded``); rank 0 is the leader (engine + frontend,
  streams tokens to the gateway), ranks 1..W-1 run the
  :class:`~tpu_dist.serve.ShardFollower` loop.  Per-block partial
  activations combine over the p2p data plane; the KV cache is sharded
  by head, no replication.  A dead shard fails the gang round (its peers
  hold the other heads), so the launcher's ordinary world restart IS the
  gang restart::

      python -m tpu_dist.launch --standalone --nproc_per_node=2 \\
          --max_restarts=3 --serve examples/serve_lm.py --tiny --sharded

- ``--disagg`` — **disaggregated prefill/decode** (tpu_dist.serve.disagg):
  launch with ``--roles prefill:P,decode:D`` so prompt bursts never stall
  in-flight decodes — prefill ranks claim prompts off the shared typed
  channel, prefill them (through the shared prefix cache on repeated
  prefixes) and ship the KV rows to the owning decode rank over the data
  plane; decode ranks admit arrived requests between iterations and
  serve the gateway, one registered backend per decode rank::

      python -m tpu_dist.launch --standalone --max_restarts=3 --serve \\
          --roles prefill:1,decode:1 examples/serve_lm.py --tiny --disagg

Self-healing wiring: the worker publishes heartbeats
(:class:`tpu_dist.resilience.Heartbeat`) with the scheduler's decode-step
count as progress, so ``--heartbeat_timeout`` converts a wedged decode
loop into a named ``RankLostError`` + supervised restart.

``--exit-on-preempt`` is the serving half of the preemption protocol
(cf. examples/elastic_train.py): on SIGTERM the worker STOPS ADMITTING,
finishes every in-flight decode (queued-but-unadmitted requests fail
with a named ``SchedulerDrainingError``), then exits
``PREEMPTED_EXIT_CODE`` (117) so an elastic supervisor re-forms without
it instead of burning restarts.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--backend", default=None, choices=["tpu", "cpu"],
                   help="demand this jax platform for the model (checked "
                        "after import — the worker refuses to serve from "
                        "any other); default: whatever JAX resolves, "
                        "reported in the 'serving on' line")
    p.add_argument("--dim", type=int, default=256)
    p.add_argument("--depth", type=int, default=4)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--vocab", type=int, default=32768)
    p.add_argument("--max-seq-len", type=int, default=1024)
    p.add_argument("--slots", type=int, default=8,
                   help="KV-cache slots = max concurrent decodes")
    p.add_argument("--cache-dtype", default="float32",
                   choices=["float32", "bfloat16", "int8"])
    p.add_argument("--port", type=int, default=0,
                   help="frontend port (0 = ephemeral; the address is "
                        "published to the store either way)")
    p.add_argument("--batch-window", type=float, default=0.004,
                   help="admission coalescing deadline, seconds")
    p.add_argument("--tiny", action="store_true",
                   help="toy model preset for tests/CI (fast compile)")
    p.add_argument("--sharded", action="store_true",
                   help="tensor-parallel decode across the launcher's "
                        "whole world (tpu_dist.serve.sharded): rank 0 "
                        "leads + serves, other ranks follow; needs the "
                        "control-plane store + num_heads %% world == 0")
    p.add_argument("--disagg", action="store_true",
                   help="disaggregated prefill/decode: run under python -m "
                        "tpu_dist.launch --serve --roles prefill:P,decode:D "
                        "— prefill ranks claim prompts off the shared "
                        "queue and ship KV rows over the data plane, "
                        "decode ranks own requests + serve the gateway "
                        "(tpu_dist.serve.disagg, docs/serving.md)")
    p.add_argument("--kv-wire", default=None,
                   help="disagg KV-transfer wire compression opt-in "
                        "(e.g. int8_block256) — lossy, so greedy parity "
                        "with generate() no longer holds; default = exact")
    p.add_argument("--prefix-block", type=int, default=16,
                   help="prefix-cache chain granularity in tokens")
    p.add_argument("--prefix-cache-mb", type=int, default=64,
                   help="prefix-cache resident byte cap, MiB (0 disables "
                        "the cache entirely)")
    p.add_argument("--prefix-spill", default=None,
                   help="page cold prefix entries to this directory "
                        "instead of evicting (restored bitwise-equal via "
                        "the reshard fragment reader; the index persists "
                        "across restarts)")
    p.add_argument("--backend-name", default="default",
                   help="this backend's name in the gateway's registry "
                        "(replicas register distinct names; a restarted "
                        "incarnation re-registers the same one)")
    p.add_argument("--comm-dtype", default=None,
                   help="sharded partial-sum wire compression opt-in "
                        "(e.g. int8_block256); default = exact f32")
    p.add_argument("--exit-on-preempt", action="store_true",
                   help="on SIGTERM: drain (finish in-flight, admit "
                        "nothing new) and exit PREEMPTED_EXIT_CODE (117)")
    p.add_argument("--run-seconds", type=float, default=0.0,
                   help="exit cleanly after N seconds (0 = run until "
                        "signalled; tests use this as a safety bound)")
    p.add_argument("--emulate-step-ms", type=float, default=0.0,
                   help="floor each decode iteration to N ms (bench/test "
                        "knob: emulates an accelerator-bound model on a "
                        "host whose CPU cannot fit one — the pacing "
                        "discipline the CRC-overhead bench established; "
                        "benchmarks/bench_serve.py --sharded uses it so "
                        "the replica-scaling row measures ROUTING, not "
                        "one core time-slicing two compute-bound "
                        "processes)")
    p.add_argument("--pid-file", default=None,
                   help="write this process's pid here once serving "
                        "(rank r > 0 appends '.r{r}'; chaos tests "
                        "SIGKILL through it)")
    return p


def _write_pid(args, rank: int) -> None:
    if args.pid_file:
        path = args.pid_file if rank == 0 else f"{args.pid_file}.r{rank}"
        with open(path, "w") as f:
            f.write(str(os.getpid()))


def _step_hook(args, hb):
    """Heartbeat progress + the optional emulated per-iteration floor."""
    if args.emulate_step_ms <= 0:
        return hb.set_step

    def hook(step):
        hb.set_step(step)
        time.sleep(args.emulate_step_ms / 1e3)
    return hook


def _serve_loop(args, sched, frontend, hb, stop, resilience,
                engine=None) -> int:
    """Rank-0 supervision loop: clean deadline exit, preemption drain,
    and the fatal-engine watch (a shard peer's death surfaces as the
    scheduler's fatal PeerGoneError → exit nonzero so the supervisor
    gang-restarts the group)."""
    deadline = (time.monotonic() + args.run_seconds
                if args.run_seconds > 0 else None)
    while deadline is None or time.monotonic() < deadline:
        if sched.fatal is not None:
            print(f"[serve_lm] decode loop died: "
                  f"{type(sched.fatal).__name__}: {sched.fatal} — "
                  f"exiting for a supervised restart", flush=True)
            frontend.close()
            hb.stop()
            return 1
        if stop is not None and stop.requested:
            # preemption: stop admitting, finish in-flight decodes,
            # then the elastic-shrink exit code.  os._exit like
            # elastic_train.py: the jax coordination service's atexit
            # teardown would block on peers mid-teardown.
            drained = sched.drain(timeout=60.0)
            if engine is not None:
                # sharded leader: release the followers with the clean
                # close plan BEFORE exiting, so they convert their own
                # SIGTERM into 117 instead of dying on PeerGoneError
                engine.close()
            print(f"[serve_lm] preempted: drained={drained}; exiting "
                  f"{resilience.PREEMPTED_EXIT_CODE}", flush=True)
            hb.stop()
            os._exit(resilience.PREEMPTED_EXIT_CODE)
        time.sleep(0.25)
    return 0


def _run_sharded(args, model, params, store, rank: int, world: int,
                 cache_dtype) -> int:
    """The tensor-parallel worker body: shard this rank's slice, join the
    shard group's data plane, and play leader (rank 0) or follower."""
    import jax  # noqa: F401  (device runtime up before the data plane)

    import importlib

    from tpu_dist import resilience, serve
    from tpu_dist.collectives.transport import DataPlane, PeerGoneError
    from tpu_dist.obs.recorder import get_recorder
    from tpu_dist.roles.graph import Role, RoleGraph, map_key, set_current

    # the module, not the same-named function the package re-exports
    rendezvous = importlib.import_module("tpu_dist.dist.rendezvous")

    if store is None:
        print("[serve_lm] --sharded needs the control-plane store "
              "(launch via python -m tpu_dist.launch, or set "
              "TPU_DIST_STORE_ADDR)", file=sys.stderr, flush=True)
        return 2
    gen = rendezvous.generation()
    # role identity for diagnostics: obs tails/dumps and the supervisor's
    # positions table read "model-shard[r]" instead of a bare flat rank
    graph = RoleGraph([Role(serve.ROLE_MODEL_SHARD, world)])
    set_current(graph, serve.ROLE_MODEL_SHARD, rank)
    rec = get_recorder()
    if rec is not None:
        rec.rank, rec.world = rank, world
        rec.role, rec.role_rank = serve.ROLE_MODEL_SHARD, rank
    if rank == 0:
        try:
            store.set(map_key(gen), graph.to_json())
        except Exception:
            pass

    dp = DataPlane(store, rank, world, generation=gen)
    decoder = serve.ShardedDecoder(
        model, serve.shard_params(model, params, rank, world), dp, rank,
        world, comm_dtype=args.comm_dtype)

    hb = resilience.Heartbeat(rank=rank)
    hb.start()
    stop = None
    if args.exit_on_preempt:
        from tpu_dist import checkpoint as ckpt
        stop = ckpt.GracefulShutdown().__enter__()
    _write_pid(args, rank)

    if rank != 0:
        follower = serve.ShardFollower(decoder, num_slots=args.slots,
                                       max_len=args.max_seq_len,
                                       cache_dtype=cache_dtype)
        hb.set_step(0)
        budget = args.run_seconds if args.run_seconds > 0 else None
        try:
            cause = follower.run(deadline=budget, plan_timeout=20.0)
        except PeerGoneError as e:
            from tpu_dist.utils.logging import log_event
            log_event("serve-shard-leader-gone", rank=rank,
                      error=repr(e))
            print(f"[serve_lm] shard follower {rank}: leader gone "
                  f"({e}) — exiting for a gang restart", flush=True)
            hb.stop()
            return 1
        print(f"[serve_lm] shard follower {rank} done ({cause}, "
              f"{follower.decode_steps} decode steps)", flush=True)
        hb.stop()
        if stop is not None and stop.requested:
            # the group closed while this rank was under a preemption
            # notice: report the preemption protocol's exit code, like
            # the leader does after its drain
            os._exit(resilience.PREEMPTED_EXIT_CODE)
        return 0

    engine = serve.ShardedSlotEngine(decoder, num_slots=args.slots,
                                     max_len=args.max_seq_len,
                                     cache_dtype=cache_dtype)
    sched = serve.Scheduler(engine, batch_window=args.batch_window,
                            step_hook=_step_hook(args, hb))
    frontend = serve.Frontend(sched, port=args.port, store=store,
                              backend_name=args.backend_name)
    print(f"[serve_lm] shard leader serving on {frontend.addr} "
          f"(world {world}, {args.slots} slots, heads/"
          f"shard {model.block0.attn.num_heads // world})", flush=True)
    rc = _serve_loop(args, sched, frontend, hb, stop, resilience,
                     engine=engine)
    frontend.close()
    sched.close()
    engine.close()
    hb.stop()
    return rc


def _run_disagg(args, model, params, cache_dtype) -> int:
    """Disaggregated worker body: every rank of ``--roles
    prefill:P,decode:D`` runs this.  Prefill ranks claim descriptors off
    the shared ``prefill-q`` channel, prefill (through the shared
    :class:`~tpu_dist.serve.PrefixCache` when it hits) and ship KV rows +
    first token to the owning decode rank; decode ranks run the
    :class:`~tpu_dist.serve.DisaggSlotEngine` pool and serve the gateway
    — each decode rank registers its own backend name, so the gateway
    load-balances across the decode group."""
    import threading

    import jax  # noqa: F401  (device runtime up before the data plane)

    from tpu_dist import resilience, serve
    from tpu_dist.collectives.transport import DataPlane
    from tpu_dist.roles.graph import parse_roles_spec
    from tpu_dist.roles.runtime import init_role_graph

    if args.cache_dtype == "int8":
        print("[serve_lm] --disagg does not support --cache-dtype int8 "
              "(transferred rows carry no scales); use --kv-wire "
              "int8_blockN to compress the WIRE instead",
              file=sys.stderr, flush=True)
        return 2
    spec = os.environ.get("TPU_DIST_ROLES")
    if not spec or not os.environ.get("TPU_DIST_STORE_ADDR"):
        print("[serve_lm] --disagg needs the role-graph launcher: "
              "python -m tpu_dist.launch --standalone --serve "
              "--roles prefill:P,decode:D examples/serve_lm.py --disagg",
              file=sys.stderr, flush=True)
        return 2
    parsed = parse_roles_spec(spec)
    if [r.name for r in parsed.roles] != [serve.ROLE_PREFILL,
                                          serve.ROLE_DECODE]:
        print(f"[serve_lm] --disagg expects --roles prefill:P,decode:D "
              f"(prefill first, the canonical disagg_graph order), got "
              f"{spec!r}", file=sys.stderr, flush=True)
        return 2
    n_prefill, n_decode = (r.world for r in parsed.roles)
    graph = serve.disagg_graph(n_prefill, n_decode)
    ctx = init_role_graph(graph)          # validates vs the published map
    rr = ctx.role_rank
    dp = DataPlane(ctx.store, ctx.rank, ctx.world,
                   generation=ctx.generation)
    # both endpoints derive the shape contract from their OWN model, so a
    # drifted geometry is a named KVTransferError, not a silent reshape
    template = serve.kv_template(
        model.init_slot_cache(1, args.max_seq_len, dtype=cache_dtype))
    kv = serve.KVTransfer(dp, template, wire=args.kv_wire)

    hb = resilience.Heartbeat(rank=ctx.rank)
    hb.start()
    stop = None
    if args.exit_on_preempt:
        from tpu_dist import checkpoint as ckpt
        stop = ckpt.GracefulShutdown().__enter__()
    _write_pid(args, ctx.rank)

    try:
        if ctx.role == serve.ROLE_PREFILL:
            prefix = None
            if args.prefix_cache_mb > 0:
                prefix = serve.PrefixCache(
                    block_tokens=args.prefix_block,
                    capacity_bytes=args.prefix_cache_mb << 20,
                    spill_dir=args.prefix_spill)
            worker = serve.PrefillWorker(
                model, params, kv,
                claim_ch=ctx.channel(serve.PREFILL_QUEUE, dp=False),
                env_chans={d: ctx.channel(serve.kv_channel(d), dp=False)
                           for d in range(n_decode)},
                rank=ctx.rank, max_len=args.max_seq_len,
                dtype=cache_dtype, prefix=prefix)
            print(f"[serve_lm] prefill[{rr}] up (rank {ctx.rank}, "
                  f"prefix cache "
                  f"{'off' if prefix is None else f'{args.prefix_cache_mb}MiB'})",
                  flush=True)
            wstop = threading.Event()
            t = threading.Thread(target=worker.run, args=(wstop,),
                                 daemon=True,
                                 name="tpu_dist-prefill-worker")
            t.start()
            deadline = (time.monotonic() + args.run_seconds
                        if args.run_seconds > 0 else None)
            while deadline is None or time.monotonic() < deadline:
                if stop is not None and stop.requested:
                    # finish the in-flight claim, then the preemption
                    # exit code — unclaimed descriptors stay on the
                    # queue for the surviving prefill ranks
                    wstop.set()
                    t.join(30.0)
                    if prefix is not None:
                        prefix.close()
                    hb.stop()
                    os._exit(resilience.PREEMPTED_EXIT_CODE)
                if not t.is_alive():
                    break               # decode side closed the queue
                hb.set_step(worker.claims)
                time.sleep(0.25)
            wstop.set()
            t.join(10.0)
            if prefix is not None:
                prefix.close()
            print(f"[serve_lm] prefill[{rr}] done: {worker.stats()}",
                  flush=True)
            return 0

        # decode rank: owns requests end to end, serves the gateway
        backend = (args.backend_name if rr == 0
                   else f"{args.backend_name}-d{rr}")
        engine = serve.DisaggSlotEngine(
            model, params, kv,
            dispatch_ch=ctx.channel(serve.PREFILL_QUEUE, dp=False),
            arrive_ch=ctx.channel(serve.kv_channel(rr), dp=False),
            num_slots=args.slots, max_len=args.max_seq_len,
            cache_dtype=cache_dtype, rank=ctx.rank, role_rank=rr)
        sched = serve.DisaggScheduler(engine,
                                      batch_window=args.batch_window,
                                      step_hook=_step_hook(args, hb))
        frontend = serve.Frontend(sched, port=args.port, store=ctx.store,
                                  backend_name=backend)
        print(f"[serve_lm] decode[{rr}] serving on {frontend.addr} as "
              f"{backend!r} ({args.slots} slots, prefill pool "
              f"{n_prefill})", flush=True)
        try:
            rc = _serve_loop(args, sched, frontend, hb, stop, resilience,
                             engine=engine)
        finally:
            frontend.close()
            sched.close()
            engine.close()
        return rc
    finally:
        hb.stop()
        try:
            dp.close()
        except Exception:
            pass
        ctx.close()


def main() -> int:
    args = build_parser().parse_args()
    if args.backend == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"   # before the first jax import

    import jax
    import jax.numpy as jnp

    import tpu_dist.dist as dist
    from tpu_dist import resilience, serve
    from tpu_dist import checkpoint as ckpt
    from tpu_dist.models import TransformerLM
    from tpu_dist.utils import ensure_compile_cache

    # the device gate: a worker started on the chip machine must not end up
    # serving from the CPU (or the reverse) without having been told to
    platform = dist.resolve_backend(args.backend)
    ensure_compile_cache()

    if args.tiny:
        args.dim, args.depth, args.heads = 64, 2, 2
        args.vocab, args.max_seq_len = 503, 192

    world = int(os.environ.get("WORLD_SIZE", "1") or 1)
    rank = int(os.environ.get("RANK", "0") or 0)

    # deterministic params (seed 0): a restarted incarnation serves the
    # same model, so resubmitted greedy requests reproduce their tokens
    model = TransformerLM(vocab_size=args.vocab, dim=args.dim,
                          depth=args.depth, num_heads=args.heads,
                          max_seq_len=args.max_seq_len)
    params = model.init(jax.random.key(0))
    cache_dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
                   "int8": jnp.int8}[args.cache_dtype]

    if args.disagg:
        # init_role_graph installs the chaos/obs hooks and connects the
        # store itself (role workers never call rendezvous)
        return _run_disagg(args, model, params, cache_dtype)

    if args.sharded:
        # shard groups never join jax.distributed: collectives ride the
        # host data plane, and the coordination service would convert one
        # shard's death into an unnamed abort of the whole group.  Arm
        # the obs crash-dump hooks ourselves (rendezvous normally does).
        from tpu_dist.obs.hooks import install_from_env
        install_from_env()
        store = serve.store_from_env()
        return _run_sharded(args, model, params, store, rank, world,
                            cache_dtype)

    # world 1 (the common serving shape today) skips the process group —
    # rendezvous adds nothing over the store the frontend already uses
    has_dist = world > 1 and "MASTER_ADDR" in os.environ
    if has_dist:
        dist.init_process_group(backend=args.backend, init_method="env://")
        rank = dist.get_rank()
    else:
        rank = 0
        # no process group at world 1 — install the flight-recorder
        # crash/exit dump handlers ourselves (rendezvous normally does
        # this), so an armed serving rank still dumps its serve spans
        from tpu_dist.obs.hooks import install_from_env
        install_from_env()
    store = serve.store_from_env()

    hb = resilience.Heartbeat()
    hb.start()
    stop = ckpt.GracefulShutdown().__enter__() if args.exit_on_preempt \
        else None   # entered for the process lifetime

    if rank != 0:
        # non-serving model rank (legacy multi-rank launch without
        # --sharded): stay alive, beat, obey the same signals
        deadline = (time.monotonic() + args.run_seconds
                    if args.run_seconds > 0 else None)
        while deadline is None or time.monotonic() < deadline:
            if stop is not None and stop.requested:
                os._exit(resilience.PREEMPTED_EXIT_CODE)
            time.sleep(0.25)
        hb.stop()
        if has_dist:
            dist.destroy_process_group()
        return 0

    engine = serve.SlotEngine(model, params, num_slots=args.slots,
                              max_len=args.max_seq_len,
                              cache_dtype=cache_dtype)
    sched = serve.Scheduler(engine, batch_window=args.batch_window,
                            step_hook=_step_hook(args, hb))
    frontend = serve.Frontend(sched, port=args.port, store=store,
                              backend_name=args.backend_name)
    print(f"[serve_lm] rank {rank} serving on {frontend.addr} "
          f"(platform {platform}, {jax.device_count()} "
          f"{jax.devices()[0].device_kind} device(s), {args.slots} slots, "
          f"max_seq_len {args.max_seq_len})", flush=True)
    _write_pid(args, rank)

    try:
        rc = _serve_loop(args, sched, frontend, hb, stop, resilience)
    except KeyboardInterrupt:
        rc = 0
    finally:
        frontend.close()
        sched.close()
        hb.stop()
        if has_dist:
            dist.destroy_process_group()
    return rc


if __name__ == "__main__":
    sys.exit(main())
