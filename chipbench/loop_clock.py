"""The loop thread's clock (``SlotEngine.stats()["loop"]``, tpu_dist.obs.spans
``LoopClock``) as a per-layer reader sees it: every iteration of the serving
loop closed by phase, CPU, garbage collection and time off the CPU, and the
longest ones whole.  A program from before the clock has no such entry: every
function here then gives None, and the harness leaves the metric out of the
line."""

from __future__ import annotations


def engine(run) -> dict | None:
    """``stats()["loop"]`` as read at the window's end; the driver's
    ``reset_stats()`` at the window's first instant zeroes it (and drops the
    iteration open across it)."""
    return run.counters.get("engine", {}).get("loop")


def share(run, key: str) -> float | None:
    """``loop[key]`` seconds over the window, in percent."""
    loop = engine(run)
    if not loop:
        return None
    t0, t1 = run.window
    return 100.0 * loop[key] / (t1 - t0)


def _say(msg: str) -> None:
    print(f"[chipbench]     {msg}", flush=True)


def say(loop: dict, top: int = 3) -> None:
    """The window's sums on one line, then the ``top`` longest iterations of
    each kind with their records."""
    _say(f"loop thread: wall {loop['wall_s']:.3f} s over "
         f"{loop['iterations']} iterations; waits {loop['wait_s']:.3f} s, "
         f"cpu {loop['cpu_s']:.3f} s, off-cpu {loop['offcpu_s']:.3f} s, "
         f"unnamed {loop['unnamed_s']:.3f} s, gc {loop['gc_s']:.3f} s in "
         f"{loop['gc_collections']} collections (gen 0, 1, 2), others' cpu "
         f"{loop['cpu_others_s']:.3f} s")
    for kind, kept in loop["longest"].items():
        for r in kept[:top]:
            phases = ", ".join(f"{n} {1e3 * s:.2f}" for n, s in sorted(
                r["by_phase"].items(), key=lambda kv: -kv[1])[:4])
            _say(f"{kind} step {r['step']} at {r['at']:.3f} s: wall "
                 f"{1e3 * r['wall']:.2f} ms in {r['phase']} ({phases}; "
                 f"unnamed {1e3 * r['unnamed']:.2f}); cpu "
                 f"{1e3 * r['cpu']:.2f}, off-cpu {1e3 * r['offcpu']:.2f}, "
                 f"gc {1e3 * r['gc']:.2f}, others' cpu "
                 f"{1e3 * r['cpu_others']:.2f} ms" + (
                     f"; switches {r['switches']} involuntary / "
                     f"{r['voluntary_switches']} voluntary, "
                     f"{r['major_faults']} major faults"
                     if "switches" in r else ""))
