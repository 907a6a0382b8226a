"""One run of one cell.

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process holds the cell's chips: it loads, warms up this cell's shapes
(set-up), measures for ``--seconds``, checks the outputs against the plain
reference outside the window, and prints as the last line of stdout one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and,
traced, ``breakdown``.  Untraced the metrics are the cell's end-to-end
metrics; traced, its per-layer metrics.

It fails, with no result, where JAX's platform is not ``tpu``, where the
``device_kind`` is not in chipbench/peaks.json, or where fewer devices are
present than the cell's ``chips``.  ``--rehearse`` lifts that for a CPU
rehearsal of the control flow and prints the line with EMPTY ``metrics``: a
number from a CPU run is never written under a device metric's name.
"""

from __future__ import annotations

from .clock import now

T_START = now()     # set-up runs from here to the window's first instant

import argparse
import importlib
import json
import math
import os
import shutil
import sys
import tempfile
import types

from . import spec


def _say(msg: str) -> None:
    print(f"[chipbench] {msg}", flush=True)


def _compile_events() -> list:
    """Instants at which a program was compiled or fetched from the cache."""
    import jax
    seen = []

    def listener(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            seen.append(now())
    jax.monitoring.register_event_duration_secs_listener(listener)
    return seen


def open_cell(benchmark: str, workload: str, seed: int, seconds: float,
              rehearse: bool = False) -> tuple:
    """Resolve a cell's files by name, place the compile cache, and refuse
    any device the benchmark does not measure.  Returns the drivers'
    context, JAX's devices and the chip's peaks (None in a rehearsal)."""
    bench = spec.load_benchmark(benchmark)
    cell = spec.named(bench["workloads"], workload, "cell")
    cfg_entry = spec.named(bench["configs"], cell["config"], "configuration")
    config = spec.load_json(os.path.join(spec.ROOT, cfg_entry["file"]))
    mix = spec.load_json(spec.find(bench, "traffic", cell["traffic"] + ".json"))

    import jax
    from tpu_dist.utils.compile_cache import ensure_compile_cache
    cache_dir = ensure_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    peak = None
    if not rehearse:
        if dev.platform != "tpu":
            raise SystemExit(f"chipbench: JAX resolved platform "
                             f"{dev.platform!r}; the benchmark measures a "
                             f"TPU and nothing else")
        peak = spec.peaks(dev.device_kind)
    if len(devices) < cell["chips"]:
        raise SystemExit(f"chipbench: cell {cell['name']!r} needs "
                         f"{cell['chips']} chips, JAX sees {len(devices)}")
    _say(f"cell {cell['name']}: {cell['config']} under {cell['traffic']} on "
         f"{cell['chips']} of {len(devices)} {dev.device_kind} "
         f"({dev.platform}); seed {seed}, {seconds:g} s; compile cache "
         f"{cache_dir}")
    from .spans import Spans
    ctx = types.SimpleNamespace(
        bench=bench, cell=cell, config=config, mix=mix, seed=seed,
        seconds=seconds, trace=False, trace_dir=None, chips=cell["chips"],
        spans=Spans(),
        mark=lambda what: _say(f"  +{now() - T_START:7.3f} s  {what}"))
    ctx.mark("imports, devices")
    return ctx, devices, peak


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python3 -m chipbench.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--benchmark", default="BENCHMARK.json",
                    help="the file that lists cells, metrics and paths")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal: any device, empty metrics")
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="keep the traced run's profile here")
    a = ap.parse_args(argv)

    ctx, devices, peak = open_cell(a.benchmark, a.workload, a.seed, a.seconds,
                                   a.rehearse)
    bench, cell, dev = ctx.bench, ctx.cell, devices[0]
    compiles = _compile_events()
    ctx.trace = bool(a.trace)
    if a.trace:
        ctx.trace_dir = a.keep_trace or tempfile.mkdtemp(
            prefix="chipbench-trace-")
    driver = importlib.import_module(
        "chipbench.drivers." + ctx.config["driver"])
    res = driver.run(ctx)
    t0, t1 = res["window"]
    used = devices[:cell["chips"]]
    memory = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in used)
    in_window = sum(t0 <= t < t1 for t in compiles)
    setup_s = t0 - T_START
    _say(f"set-up {setup_s:.3f} s; window {t1 - t0:.3f} s; programs "
         f"compiled or loaded inside it: {in_window}; peak bytes on the "
         f"fullest chip {memory}")

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory}
    out = {"correct": None, "attempted": res["attempted"],
           "failed": res["failed"], "metrics": {}, "device": device}
    if a.trace:
        from . import trace_reduce
        reduced = trace_reduce.reduce(
            trace_reduce.load(trace_reduce.find_xplane(ctx.trace_dir)))
        if not a.keep_trace:
            shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        if reduced:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            out["breakdown"] = trace_reduce.breakdown(reduced)
        run = types.SimpleNamespace(
            ctx=ctx, trace=reduced, spans=ctx.spans, window=(t0, t1),
            counters=dict(res["counters"], compiles_in_window=in_window),
            client=res.get("client"), model_kwargs=res.get("model_kwargs"),
            peak=peak)
        listed = [m for m in bench["per_layer"]
                  if spec.applies(m, cell["name"])]
        values = {m["name"]: spec.load_module(spec.find(
            bench, "layer_metrics", m["name"] + ".py")).read(run)
            for m in listed}
    else:
        listed = [m for m in bench["end_to_end"]
                  if spec.applies(m, cell["name"])]
        values = dict(res["end_to_end"], setup_s=setup_s)
    for m in listed:
        v = values.get(m["name"])
        _say(f"  {m['name']}: {v} {m['unit']}")
        if v is None and not a.trace:
            raise SystemExit(f"chipbench: driver {ctx.config['driver']!r} "
                             f"does not measure {m['name']}")
        if v is not None and not math.isfinite(v):
            raise SystemExit(f"chipbench: {m['name']} is {v}: the window "
                             f"held nothing to measure it on")
        if v is not None and not a.rehearse:
            out["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}

    out["correct"] = bool(res["verify"]())
    res["close"]()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
