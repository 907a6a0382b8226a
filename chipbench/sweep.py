"""Find the knee of an open-loop mix: once, when a cell is defined.

    python3 -m chipbench.sweep --workload <cell> --seed <n> --seconds <s> \\
        --rates 3,4,5,6

One server, built and warmed once; one window and one fresh load generator per
rate, the mix unchanged but for ``rate_per_s``.  The knee is the highest rate
at which the backlog (requests due and not finished) at the window's end is
no larger than at its middle.  The cell's file then fixes a rate at about
four fifths of it; run.py never searches.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import tempfile

from .drivers import serve
from .run import open_cell


def main() -> int:
    ap = argparse.ArgumentParser(prog="python3 -m chipbench.sweep")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True, help="comma-separated, per s")
    ap.add_argument("--benchmark", default="BENCHMARK.json")
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args()

    ctx, _, _ = open_cell(a.benchmark, a.workload, a.seed, a.seconds,
                          a.rehearse)
    server = serve.build(ctx)
    rows = []
    try:
        serve.warm_up(ctx, server)
        for i, rate in enumerate(float(r) for r in a.rates.split(",")):
            at = copy.copy(ctx)
            at.mix, at.seed = dict(ctx.mix, rate_per_s=rate), a.seed + i
            with tempfile.NamedTemporaryFile(
                    "w", suffix=".json", delete=False) as f:
                json.dump(at.mix, f)
            try:
                m = serve.measure(at, server, serve.start_generator(
                    at, server, f.name))
            finally:
                os.unlink(f.name)
            s = serve.summarise(at, m, server["vocab"])
            rows.append((rate, s))
    finally:
        serve.close(server)
    print("rate/s due failed backlog_mid backlog_end ttft_p50 ttft_p95 "
          "itl_p50 itl_p95 gen_tok/s 2nd_half")
    for rate, s in rows:
        print(f"{rate:6.2f} {s['attempted']:4d} {s['failed']:4d} "
              f"{s['backlog_mid']:6d} {s['backlog_end']:6d} "
              f"{s['ttft_p50_ms']:9.2f} {s['ttft_p95_ms']:9.2f} "
              f"{s['itl_p50_ms']:8.2f} {s['itl_p95_ms']:8.2f} "
              f"{s['generated_tokens_per_s']:9.1f} "
              f"{s['generated_tokens_per_s_2nd_half']:8.1f}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
