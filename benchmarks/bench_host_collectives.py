"""Benchmark: host-collective throughput, store transport vs. p2p data plane.

Measures MB/s for the eager host collectives (all_reduce / all_gather /
broadcast) per payload size and world size, over both transports:

- **store** — the control-plane TCPStore path (pickled payloads through the
  single central server; ``TPU_DIST_DP_THRESHOLD`` forced huge);
- **dataplane** — the rank↔rank socket data plane running the
  chunk-pipelined ring / tree collectives (threshold forced to 0).

Each world size gets a fresh store server hosted by this driver; workers
are plain processes (``--worker`` mode of this same file) wired exactly as
the eager collectives see production (store client + rendezvous store
injection), no XLA involvement — this benchmarks the host transports, not
the compiler.

MB/s is *algorithmic* bandwidth: input payload bytes per second of
collective wall time (the quantity the ISSUE 2 acceptance compares; the
ring moves 2(N-1)/N of that on the wire per rank, the store path moves up
to N× through one process).

The dataplane all-reduce additionally runs **wire-compression** variants
(``comm``: plain f32, ``bfloat16`` cast, ``int8_block256`` block
quantization — tpu_dist/collectives/quant.py): same logical payload,
compressed frames on the wire.  MB/s stays *effective* (logical bytes per
second — the quantity the ISSUE 8 acceptance compares), and each row
carries the measured wire-byte ``compression`` ratio from the transport
counters.

**Topology variants** (ISSUE 9): the dataplane all-reduce also runs as
``algo``: ``flat`` (the flat TCP ring — SHM lanes off, the baseline every
prior measurement used), ``flat_shm`` (same flat ring, shared-memory
intra-host payload lanes — the TCP-vs-SHM isolate), and ``hier`` (the
two-level host-major ring over SHM lanes —
tpu_dist/collectives/topology.py).  Workers get simulated host
fingerprints (``TPU_DIST_HOST_ID``): world >= 4 splits into 2 "hosts"
host-contiguously (the 2-host x 2-rank acceptance layout), smaller worlds
share one.  The final ``hier_vs_flat_speedup_8MiB_w{world}`` summary is
the ISSUE 9 acceptance (>= 1.5x over the flat TCP ring); ``--smoke``
additionally cross-checks hierarchical numerics BITWISE against the flat
ring and compares result digests across ranks.

Prints one BENCH-style JSON line per measurement::

    {"metric": "host_collective", "op": "all_reduce", "path": "dataplane",
     "comm": "int8_block256", "world": 4, "bytes": 8388608, "value": 47.3,
     "compression": 3.88, "unit": "MB/s"}

plus final summary lines: ``ring_vs_store_speedup_8MiB_w4`` (the ISSUE 2
acceptance: >= 3) and ``quant_vs_f32_speedup_8MiB_w4`` (the ISSUE 8
acceptance: >= 2× effective MB/s over the uncompressed ring).  ``--smoke``
runs world=2 with one 1 MiB payload, a numeric cross-check, and a
cross-rank byte-identity check of the quantized all-reduce, in seconds —
wired as a tier-1 test so the data plane is exercised on every PR.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import subprocess
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SMOKE_SIZES = [1 << 20]
_FULL_SIZES = [64 << 10, 1 << 20, 8 << 20]
_OPS = ("all_reduce", "all_gather", "broadcast")


# ---------------------------------------------------------------------------
# worker
# ---------------------------------------------------------------------------

def _worker() -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy as np

    from tpu_dist.dist.store import TCPStore

    rank = int(os.environ["RANK"])
    world = int(os.environ["WORLD_SIZE"])
    spec = json.loads(os.environ["BENCH_SPEC"])
    host, _, port = os.environ["TPU_DIST_STORE_ADDR"].rpartition(":")
    store = TCPStore(host, int(port))
    # the eager collectives discover the control-plane store through the
    # rendezvous module (import via importlib: the name `rendezvous` in
    # tpu_dist.dist is the re-exported *function*)
    rdzv = importlib.import_module("tpu_dist.dist.rendezvous")
    rdzv._store = store

    class _Group:
        """Process-identity shim: the store/data-plane collective paths
        need only rank + num_processes (no mesh, no jax.distributed)."""
        def __init__(self, rank, num_processes):
            self.rank, self.num_processes = rank, num_processes

    g = _Group(rank, world)
    from tpu_dist import collectives as C

    def run_op(op, x):
        if op == "all_reduce":
            return C.all_reduce_host(x, group=g, op="sum")
        if op == "all_gather":
            return C.all_gather_host(x, group=g)
        if op == "broadcast":
            return C.broadcast_host(x, group=g, src=0)
        raise ValueError(op)

    from tpu_dist.obs import recorder as _rec

    def apply_case_env(case):
        os.environ["TPU_DIST_DP_THRESHOLD"] = (
            "0" if case["path"] == "dataplane" else str(1 << 60))
        if case.get("comm"):
            os.environ["TPU_DIST_COMM_DTYPE"] = case["comm"]
        else:
            os.environ.pop("TPU_DIST_COMM_DTYPE", None)
        # frame-integrity variant: checksum on (the default) vs off — the
        # crc_overhead summary is their ratio.  Plain rows keep the
        # environment default (armed), matching production.
        if case.get("crc") is not None:
            os.environ["TPU_DIST_FRAME_CRC"] = case["crc"]
        else:
            os.environ.pop("TPU_DIST_FRAME_CRC", None)
        # wire emulation for the crc gate rows: BOTH arms paced to the
        # same fixed rate by a netchaos slow-drip fault — the production
        # regime is a wire-bound link where checksum arithmetic overlaps
        # transfer; this box's loopback is CPU/memory-bound, so an
        # unpaced comparison measures memory-bus contention (~1:1 for
        # any added pass), not the deployed cost of integrity
        from tpu_dist.resilience import netchaos as _netchaos
        rate = case.get("wire_rate")
        if rate:
            _netchaos.install(f"slow-drip:surface=tcp,rate={int(rate)}")
        else:
            _netchaos.uninstall()
        # topology variants: algo picks the ring shape, shm the intra-host
        # payload transport.  Plain rows pin algo=flat + SHM off so the
        # baseline stays the flat TCP ring every prior round measured.
        algo = case.get("algo", "flat")
        os.environ["TPU_DIST_ALGO"] = "hier" if algo == "hier" else "flat"
        os.environ["TPU_DIST_SHM"] = (
            "auto" if algo in ("hier", "flat_shm") else "0")

    rows = []
    for ci, case in enumerate(spec["cases"]):
        nbytes, op, path, iters = (case["bytes"], case["op"], case["path"],
                                   case["iters"])
        comm = case.get("comm")
        algo = case.get("algo", "flat")
        x = (np.random.default_rng(1000 + rank)
             .standard_normal(nbytes // 4).astype(np.float32))
        if case.get("crc_paired"):
            # the CRC gate is PAIRED: each rep times the checksum-armed
            # arm and the disarmed arm back-to-back on the same emulated
            # wire, so a suite-load spike lands on both arms of its pair
            # and cancels in the ratio; the median per-pair overhead is
            # what the tier-1 gate asserts.  (The former best-of-N
            # per-arm comparison ran the arms seconds apart and drifted
            # with background load — the retried tier-1 flake.)
            reps = max(1, int(case.get("reps", 1)))
            apply_case_env(dict(case, crc="1"))
            run_op(op, x)  # warm-up: opens peer connections
            arm_t = {"1": [], "0": []}
            for rep in range(reps):
                # ABBA order: whichever arm runs second in a pair starts
                # with warmer caches/sockets; alternating cancels that
                # systematic edge across pairs instead of baking it in
                order = ("1", "0") if rep % 2 == 0 else ("0", "1")
                for crc in order:
                    apply_case_env(dict(case, crc=crc))
                    store.barrier(world, tag=f"crcp/{ci}/{rep}/{crc}")
                    t0 = time.perf_counter()
                    for _ in range(iters):
                        run_op(op, x)
                    arm_t[crc].append(time.perf_counter() - t0)
            pair_pcts = sorted((on - off) / off * 100.0
                               for on, off in zip(arm_t["1"], arm_t["0"]))
            mid = len(pair_pcts) // 2
            med = (pair_pcts[mid] if len(pair_pcts) % 2
                   else (pair_pcts[mid - 1] + pair_pcts[mid]) / 2)
            rows.append({
                "metric": "crc_paired", "op": op, "world": world,
                "bytes": nbytes, "iters": iters, "pairs": reps,
                "wire_mb_s": case.get("wire_rate", 0) // 1_000_000,
                "value": round(max(0.0, med), 2), "unit": "%",
                "pair_pcts": [round(p, 2) for p in pair_pcts],
                "on_mb_s": round(nbytes * iters / min(arm_t["1"]) / 1e6,
                                 2),
                "off_mb_s": round(nbytes * iters / min(arm_t["0"]) / 1e6,
                                  2)})
            continue
        apply_case_env(case)
        out = run_op(op, x)  # warm-up: opens peer connections, primes numpy
        if spec.get("check") and op == "all_reduce" \
                and case.get("crc") is None:
            # every rank takes the same branch (case fields are shared),
            # so the reference collectives stay rank-aligned
            if algo in ("hier", "flat_shm"):
                # the ISSUE 9 acceptance property: hierarchical (and the
                # SHM transport) results are BITWISE-equal to the flat
                # TCP ring on the host-contiguous layout
                apply_case_env({"path": "dataplane"})
                flat = run_op(op, x)
                assert np.array_equal(np.asarray(out), np.asarray(flat)), \
                    f"{algo} result != flat ring bitwise"
            else:
                os.environ.pop("TPU_DIST_COMM_DTYPE", None)
                os.environ["TPU_DIST_DP_THRESHOLD"] = str(1 << 60)
                ref = run_op(op, x)  # store-path reference
                if comm:
                    # lossy wire: bounded relative error, and — the
                    # property compression must never cost —
                    # byte-identical results on every rank (digests
                    # compared through the store)
                    err = float(np.max(np.abs(np.asarray(out) - ref)))
                    bound = float(np.max(np.abs(ref))) * (
                        0.1 if comm.startswith("int8") else 0.02)
                    assert err <= bound, (comm, err, bound)
                else:
                    np.testing.assert_allclose(out, ref, rtol=2e-6,
                                               atol=1e-5)
            if comm or algo in ("hier", "flat_shm"):
                import hashlib
                dig = hashlib.sha256(np.ascontiguousarray(out).tobytes()) \
                    .hexdigest().encode()
                store.set(f"bench/qdig/{ci}/{rank}", dig)
                store.barrier(world, tag=f"qdig{ci}")
                digs = {store.get(f"bench/qdig/{ci}/{r}")
                        for r in range(world)}
                assert len(digs) == 1, "rank-divergent collective result"
            apply_case_env(case)
        # best-of-reps against 2-core scheduler noise (the
        # bench_obs_overhead discipline: max-MB/s aggregation — identical
        # configs otherwise swing +-50% run to run on this box)
        reps = max(1, int(case.get("reps", 1)))
        tag = f"{op}/{path}/{comm}/{algo}/{nbytes}"
        best, counters = None, None
        for rep in range(reps):
            store.barrier(world, tag=f"{tag}/r{rep}")
            _rec.reset_transport_counters()
            t0 = time.perf_counter()
            for _ in range(iters):
                run_op(op, x)
            dt = time.perf_counter() - t0
            c = _rec.transport_counters(reset=True).get(f"{op}/{path}")
            v = nbytes * iters / dt / 1e6
            if best is None or v > best:
                best, counters = v, c
        row = {"metric": "host_collective", "op": op, "path": path,
               "world": world, "bytes": nbytes, "iters": iters,
               "reps": reps, "comm": comm or "f32", "algo": algo,
               "value": round(best, 2), "unit": "MB/s"}
        if counters:
            row["compression"] = round(counters["compression"], 2)
        rows.append(row)
    for key in ("TPU_DIST_COMM_DTYPE", "TPU_DIST_ALGO", "TPU_DIST_SHM",
                "TPU_DIST_FRAME_CRC"):
        os.environ.pop(key, None)
    from tpu_dist.resilience import netchaos as _netchaos
    _netchaos.uninstall()
    if rank == 0:
        with open(os.environ["BENCH_OUT"], "w") as f:
            json.dump(rows, f)
    store.barrier(world, tag="bench-exit")
    store.close()
    return 0


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def _iters_for(nbytes: int, path: str) -> int:
    # enough repetitions to average out scheduler noise without letting the
    # slow store path at 8 MiB dominate the wall clock
    if path == "store":
        return 3 if nbytes >= (1 << 20) else 6
    return 6 if nbytes >= (1 << 20) else 12


def _reps_for(path: str, smoke: bool) -> int:
    # dataplane rows take best-of-3 (cheap, and the acceptance ratios live
    # there); the store path is too slow to repeat and not ratio-gated
    if smoke or path == "store":
        return 1
    return 3


def _run_world(world: int, sizes, iters_override, check: bool,
               out_path: str):
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from tpu_dist.dist.store import TCPStore

    cases = [{"op": op, "path": path, "bytes": nbytes, "comm": None,
              "reps": _reps_for(path, check),
              "iters": iters_override or _iters_for(nbytes, path)}
             for op in _OPS
             for nbytes in sizes
             for path in ("store", "dataplane")]
    # wire-compression variants of the dataplane ring all-reduce: bf16
    # cast vs int8 block quantization vs the plain-f32 row above
    cases += [{"op": "all_reduce", "path": "dataplane", "bytes": nbytes,
               "comm": comm, "reps": _reps_for("dataplane", check),
               "iters": iters_override or _iters_for(nbytes, "dataplane")}
              for nbytes in sizes
              for comm in ("bfloat16", "int8_block256")]
    # topology variants: flat ring over SHM lanes (TCP-vs-SHM isolate) and
    # the hierarchical two-level ring (the ISSUE 9 acceptance rows)
    cases += [{"op": "all_reduce", "path": "dataplane", "bytes": nbytes,
               "comm": None, "algo": algo,
               "reps": _reps_for("dataplane", check),
               "iters": iters_override or _iters_for(nbytes, "dataplane")}
              for nbytes in sizes
              for algo in ("flat_shm", "hier")]
    # frame-integrity (CRC) overhead isolate at the 8 MiB gate size: the
    # SAME flat dataplane all-reduce with checksums armed (the default)
    # vs disarmed, measured PAIRED (each rep times both arms back to
    # back; the worker reports the median per-pair overhead), both arms
    # paced to an identical emulated wire rate (netchaos slow-drip — see
    # apply_case_env) so the row measures integrity's cost in the
    # wire-bound regime the data plane deploys into.  The crc_overhead
    # summary carries its 5% threshold beside the value.
    cases += [{"op": "all_reduce", "path": "dataplane", "bytes": 8 << 20,
               "comm": None, "crc_paired": True, "reps": 7,
               "wire_rate": 150_000_000,
               "iters": iters_override or 2}]
    # simulated host layout (host-contiguous): world >= 4 splits into two
    # "hosts" (the 2-host x 2-rank acceptance layout at world 4); smaller
    # worlds co-locate on one, so SHM lanes exist at every world
    nhosts = 2 if world >= 4 else 1

    store = TCPStore(is_master=True)
    procs = []
    try:
        env = dict(os.environ,
                   TPU_DIST_STORE_ADDR=f"127.0.0.1:{store.port}",
                   WORLD_SIZE=str(world),
                   PYTHONPATH=_REPO + os.pathsep + os.environ.get(
                       "PYTHONPATH", ""),
                   JAX_PLATFORMS="cpu",
                   BENCH_OUT=out_path,
                   BENCH_SPEC=json.dumps({"cases": cases, "check": check}))
        env.pop("TPU_DIST_RESTART_COUNT", None)
        procs = [subprocess.Popen(
            [sys.executable, "-m", "benchmarks.bench_host_collectives",
             "--worker"],
            env=dict(env, RANK=str(r),
                     TPU_DIST_HOST_ID=f"h{r * nhosts // world}"),
            cwd=_REPO)
            for r in range(world)]
        deadline = time.monotonic() + (600 if check else 1800)
        rcs = [p.wait(timeout=max(1, deadline - time.monotonic()))
               for p in procs]
        if any(rcs):
            raise RuntimeError(f"bench workers failed: rcs={rcs}")
    finally:
        for p in procs:  # a hung/failed world must not leak workers
            if p.poll() is None:
                p.kill()
                p.wait()
        store.close()
    with open(out_path) as f:
        return json.load(f)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--worker", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--smoke", action="store_true",
                    help="world=2, one 1 MiB payload, numeric cross-check; "
                         "seconds (the tier-1 configuration)")
    ap.add_argument("--worlds", type=int, nargs="*", default=None)
    ap.add_argument("--sizes", type=int, nargs="*", default=None,
                    help="payload bytes (default 64KiB/1MiB/8MiB)")
    ap.add_argument("--iters", type=int, default=0,
                    help="override per-case iterations (0 = auto)")
    args = ap.parse_args(argv)
    if args.worker:
        return _worker()

    worlds = args.worlds or ([2] if args.smoke else [2, 4])
    sizes = args.sizes or (_SMOKE_SIZES if args.smoke else _FULL_SIZES)
    all_rows = []
    import tempfile
    for world in worlds:
        with tempfile.NamedTemporaryFile(mode="w", suffix=".json",
                                         delete=False) as tmp:
            out_path = tmp.name
        try:
            rows = _run_world(world, sizes, args.iters, check=args.smoke,
                              out_path=out_path)
        finally:
            try:
                os.unlink(out_path)
            except OSError:
                pass
        for row in rows:
            if args.smoke:
                row["smoke"] = True
            print(json.dumps(row))
        all_rows.extend(rows)

    # the ISSUE 2 / ISSUE 8 / ISSUE 9 acceptance quantities, when measured
    # (guarded by metric: the crc_paired row shares op/world/bytes with
    # the plain 8 MiB row and would silently replace it)
    by_key = {(r["op"], r["path"], r.get("comm", "f32"),
               r.get("algo", "flat"), r["world"], r["bytes"]): r["value"]
              for r in all_rows if r.get("metric") == "host_collective"}
    # ISSUE 13: frame-checksum overhead at 8 MiB — armed (the production
    # default) against disarmed, as the median of back-to-back paired
    # reps.  A time on a shared CPU: printed beside its threshold, and
    # no run fails by it
    crc_rows = {r["world"]: r for r in all_rows
                if r.get("metric") == "crc_paired"
                and r["bytes"] == 8 << 20}
    for world in worlds:
        r = crc_rows.get(world)
        if r:
            print(json.dumps({"metric": f"crc_overhead_8MiB_w{world}",
                              "value": r["value"], "unit": "%",
                              "threshold": 5.0, "pairs": r["pairs"],
                              "estimator": "paired-median"}))
    ring = by_key.get(("all_reduce", "dataplane", "f32", "flat", 4,
                       8 << 20))
    store_v = by_key.get(("all_reduce", "store", "f32", "flat", 4,
                          8 << 20))
    if ring and store_v:
        print(json.dumps({"metric": "ring_vs_store_speedup_8MiB_w4",
                          "value": round(ring / store_v, 2),
                          "unit": "x", "threshold": 3.0}))
    # quant acceptance at every measured world: on hardware where the wire
    # is the bottleneck compression wins at any world size; on this 2-core
    # sandbox world>cores serializes the ranks and CPU contention inverts
    # it (even the pre-existing bf16 cast wire measures below f32 there),
    # so the per-world rows tell the honest story — see
    # docs/collectives.md §quantized
    for world in worlds:
        ring_w = by_key.get(("all_reduce", "dataplane", "f32", "flat",
                             world, 8 << 20))
        quant_w = by_key.get(("all_reduce", "dataplane", "int8_block256",
                              "flat", world, 8 << 20))
        if ring_w and quant_w:
            print(json.dumps(
                {"metric": f"quant_vs_f32_speedup_8MiB_w{world}",
                 "value": round(quant_w / ring_w, 2),
                 "unit": "x", "threshold": 2.0}))
        # ISSUE 9 acceptance: the two-level SHM ring vs the flat TCP ring
        # (>= 1.5x at 8 MiB on the simulated 2-host x 2-rank world-4
        # layout); results bitwise-equal, checked in --smoke
        hier_w = by_key.get(("all_reduce", "dataplane", "f32", "hier",
                             world, 8 << 20))
        if ring_w and hier_w:
            print(json.dumps(
                {"metric": f"hier_vs_flat_speedup_8MiB_w{world}",
                 "value": round(hier_w / ring_w, 2),
                 "unit": "x", "threshold": 1.5}))
    return 0


if __name__ == "__main__":
    sys.path.insert(0, _REPO)
    sys.exit(main())
