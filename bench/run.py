"""Run one benchmark cell on the chips of this machine.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace 0|1

The cell is an entry of ``BENCHMARK.json``'s ``workloads``.  Set-up (made
from ``--seed``: data, weights, warm-up of every program the window will
run) is timed as ``setup_s``; then the window runs for ``--seconds``;
then the plain reference checks what the window's programs produced.  The
last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics read from a profiler trace of
the window), ``device`` and, last, ``checks``: every number compared
beside its limit.  Without a TPU, with fewer chips than the cell asks
for, or without the program's source beside this directory, it exits
non-zero and prints no result.
"""

from __future__ import annotations

import argparse
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
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no program source at {ROOT / 'src'}; run from a "
              f"checkout of the repository", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    # the compile cache lives at a fixed path inside the checkout unless
    # the environment names one; the program's own cache switch reads it
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(ROOT / ".jax_cache"))
    from bench import harness

    bench = harness.benchmark()
    try:
        w = harness.cell(args.workload, bench)
    except KeyError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        devs = harness.devices(w["chips"])
    except (harness.NoChip, RuntimeError) as e:
        print(f"bench: {e}; this benchmark runs only on the chip",
              file=sys.stderr)
        return 3
    from repro import compile_cache
    harness.say(f"{len(devs)} x {devs[0].device_kind}; compile cache "
                f"{compile_cache.enable()}")
    out = harness.run_cell(args.workload, args.seed, args.seconds,
                           bool(args.trace), devs, t_start, bench=bench)
    print(out["line"], flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
