"""Find the knee of a serving cell: offered rate against p95 latency,
answered share and backlog, one set-up and several open-loop windows.

    python bench/sweep.py --workload serve.lda-nytimes.predict \\
        --seed <n> --seconds 8 --rates 100 200 400 800

Run it once on the chip when a serving cell is defined; the cell's
traffic file then fixes a rate at about four fifths of the highest rate
whose backlog does not grow.  Prints one JSON line per rate.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(ROOT / ".jax_cache"))
    import numpy as np
    from bench import harness, serve
    w = harness.cell(args.workload)
    devs = harness.devices(w["chips"])
    from repro import compile_cache
    compile_cache.enable()
    ctx = harness.Ctx(args.workload, harness.config(w["config"]),
                      harness.traffic(w["traffic"]), args.seed,
                      args.seconds, False, devs, harness.CompileLog(),
                      t_start)
    with serve.Service(ctx) as svc:
        for rate in args.rates:
            tr = dict(ctx.traffic, rate_per_s=rate)
            sched = serve.Schedule(ctx.cfg, tr, args.seed, args.seconds,
                                   svc.maps)
            r = svc.drive(sched, args.seconds, False)
            lat = r["lat"]
            done = r["done_at"] <= r["win"].t1
            d = r["s1"]["docs"] - r["s0"]["docs"]
            b = max(r["s1"]["batches"] - r["s0"]["batches"], 1)
            print(json.dumps({
                "rate_per_s": rate, "offered": len(sched.due),
                "answered_in_window": int(done.sum()),
                "answered_share": float(done.sum()) / len(sched.due),
                "failed": int(np.isnan(lat).sum()),
                "backlog_at_close": r["backlog"],
                "p50_ms": float(np.nanpercentile(lat, 50)) * 1e3,
                "p95_ms": float(np.nanpercentile(lat, 95)) * 1e3,
                "late_max_ms": float(r["late"].max()) * 1e3,
                "batch_docs": d / b}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
