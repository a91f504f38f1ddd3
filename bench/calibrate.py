"""Readings of the numbers that decide ``correct``, on several seeds in
one process: of sound runs, of the control, or of a planted fault.

    python bench/calibrate.py --workload <cell> [--seconds 0] \\
        --modes control fault:<name> ... --seeds 11 12 13

The control is the nearest precision below the configuration's float32:
for training, the program's own narrow-table path (``SVIConfig
.elog_dtype="bfloat16"``); for serving, which has no such path, the
reference computed in bfloat16 in the program's place.  Faults are those
of ``bench/faults.py``, planted in the program.  The benchmark's own runs
never run this; the limits in ``limits/<cell>.json`` are set from what it
prints (see PERF.md).  One JSON line per seed.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=0.0,
                    help="window length; 0 runs none (training)")
    ap.add_argument("--modes", nargs="+", default=["sound"],
                    help="sound, control or fault:<name>, each run on "
                         "every seed")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(ROOT / ".jax_cache"))
    from bench import faults, harness
    w = harness.cell(args.workload)
    devs = harness.devices(w["chips"])
    from repro import compile_cache
    compile_cache.enable()
    driver = harness.traffic(w["traffic"])["driver"]
    for mode in args.modes:
        kw, plant = {}, contextlib.nullcontext
        if mode == "control":
            kw = ({"elog_dtype": "bfloat16"} if driver == "train"
                  else {"reference_dtype": "bfloat16"})
        elif mode.startswith("fault:"):
            table = faults.TRAIN if driver == "train" else faults.SERVE
            plant = table[mode.split(":", 1)[1]]
        for seed in args.seeds:
            t0 = time.perf_counter()
            row = {"workload": args.workload, "mode": mode, "seed": seed}
            try:
                with plant():
                    out = harness.run_cell(args.workload, seed,
                                           args.seconds, False, devs, t0,
                                           **kw)
                row.update(correct=out["correct"], failed=out["failed"],
                           readings=out["readings"])
            except Exception as e:   # a run that crashes gives no number
                row["error"] = f"{type(e).__name__}: {e}"[:2000]
            row["seconds"] = time.perf_counter() - t0
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
