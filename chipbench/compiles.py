"""The program's compile ledger (tpu_dist.obs.compiles) as a per-layer reader
sees it: one record a program JAX traced, lowered and compiled or loaded from
the persistent cache, with seconds by stage, what the cache said and the
``td/`` span the program fell in.  Set-up is what ended before the window's
first instant (the ledger's ``at`` and the window are both on
CLOCK_MONOTONIC).  A program from before the ledger has none: ``setup`` then
gives None, and the harness leaves the metric out of the line."""

from __future__ import annotations

from . import phases

SETUP_PHASES = ("setup.devices", "setup.init_state", "setup.place_params",
                "setup.init_cache", "setup.build_programs")


def setup(run) -> dict | None:
    """``tpu_dist.obs.compiles(until=<the window's first instant>)``: totals
    and records of the programs finished in set-up, longest first; read once
    a run."""
    if not hasattr(run, "setup_compiles"):
        import tpu_dist.obs
        read = getattr(tpu_dist.obs, "compiles", None)
        run.setup_compiles = read and read(until=run.window[0])
    return run.setup_compiles


def _say(msg: str) -> None:
    print(f"[chipbench]     {msg}", flush=True)


def say(ledger: dict, top: int = 8) -> None:
    """The totals on one line beside the ``setup.*`` phases, the cache
    directory, then the ``top`` longest programs."""
    _say(f"set-up built {ledger['programs']} programs: trace "
         f"{ledger['trace_s']:.3f} s, lower {ledger['lower_s']:.3f} s, "
         f"compile or load {ledger['backend_s']:.3f} s; cache hits "
         f"{ledger['hits']} (retrieval {ledger['retrieval_s']:.3f} s, saved "
         f"{ledger['saved_s']:.3f} s), misses {ledger['misses']} "
         f"({ledger['kept']} then kept), not asked {ledger['off']}"
         + ("; TRUNCATED: older records were dropped"
            if ledger["truncated"] else ""))
    spans = phases.process(SETUP_PHASES) or {}
    _say("phases: " + ", ".join(
        f"{n} {phases.seconds(spans, (n,)):.3f} s x {spans[n]['count']}"
        for n in SETUP_PHASES if spans[n]["count"]))
    _say(f"compile cache {ledger['cache_dir'] or '(off)'}: "
         f"{ledger['cache_bytes']} bytes in {ledger['cache_entries']} "
         f"entries (cap {ledger['cache_max_bytes']})")
    for r in ledger["records"][:top]:
        _say(f"{r['name']}: trace {r['trace_s']:.3f} s (inner "
             f"{r['inner_trace_s']:.3f}), lower {r['lower_s']:.3f} s, "
             f"compile or load {r['backend_s']:.3f} s, cache {r['cache']}"
             + (f" (retrieval {r['retrieval_s']:.3f} s)"
                if r["cache"] == "hit" else "")
             + (", kept" if r["kept"] else "")
             + f"; in {r['span'] or 'no span'}"
             + (f", step {r['step']}" if "step" in r else ""))
    # a miss the cache then kept was compiled anew and is held from now on;
    # one it did not keep (under JAX's floor) misses at every start
    kept = [r for r in ledger["records"] if r["kept"]]
    small = [r for r in ledger["records"]
             if r["cache"] == "miss" and not r["kept"]]
    if kept:
        _say("misses the cache then kept: " + ", ".join(
            f"{r['name']} {r['backend_s']:.3f} s" for r in kept))
    if small:
        _say(f"misses it does not keep: {len(small)} programs, compiled in "
             f"{sum(r['backend_s'] for r in small):.3f} s in all, the longest "
             f"{small[0]['name']} {max(r['backend_s'] for r in small):.3f} s")
