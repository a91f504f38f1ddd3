"""Benchmark registry — one module per paper table/figure.

    bench_vmp        Figure 17 + Table 4 (overall time, stage breakdown,
                     EM-LDA/MLlib baseline)
    bench_scaling    Figures 18-19 (scale-up / scale-out)
    bench_partition  Figure 20 + Tables 1-2 (partition strategies, analytic
                     + measured, replicated-memory anecdote)
    bench_kernels    VMP hot-loop primitives (fused zstats vs the unfused
                     gather+zstep+segment_sum chain)
    bench_svi        streaming SVI vs full-batch VMP at 4x the largest
                     full-batch corpus (held-out ELBO target + working set)
    bench_outofcore  sharded on-disk corpus at 8x bench_svi's, streamed to
                     the same held-out ELBO target at a bounded resident
                     working set (+ bitwise sharded-vs-resident check)
    bench_query      query/serving layer: fold-in throughput sweep across
                     batch sizes, cold-vs-warm compile, batched-vs-single
                     speedup (the serving acceptance bar)
    bench_streaming  always-on loop: append-while-training to the resident
                     held-out target (growing sampler + live commits) and
                     >= 3 hot artifact swaps under concurrent client load
                     (swap install latency, zero dropped requests)
    bench_recovery   crash-safety cost: checkpoint overhead on the training
                     loop, per-commit ms of a self-validating session save,
                     crash-to-training-again resume latency, writer reopen
    bench_multihost  multi-host SVI on bench_outofcore's corpus: single vs
                     2-virtual-host vs real 2-process (gloo) topologies —
                     us/step + tokens/s scaling and the per-host working
                     set (owned shards only)
    bench_gateway    multi-tenant gateway: mixed QL load over two
                     artifacts through admission control, and the
                     compacted-replica trade (size ratio, per-kind
                     latency, measured error bound vs realized PREDICT
                     deviation)

Prints ``name,us_per_call,derived`` CSV.  Select modules with
``python -m benchmarks.run [vmp|scaling|partition|kernels] ...``.

``--json`` additionally writes one ``BENCH_<module>.json`` per selected
module — ``{"module", "backend", "device", "rows": [{"name",
"us_per_call", "derived", ...}]}`` — the machine-readable perf trajectory
CI uploads as an artifact so regressions are diffable across commits.
``device`` is the platform, kind and count jax reports for this process.

The modules that measure fake CPU devices (scaling, partition, multihost)
run them in child processes pinned to ``JAX_PLATFORMS=cpu``, so a parent
that holds a TPU never shares it with a child; a child that fails fails
the run.
"""

from __future__ import annotations

import json
import sys


def main() -> None:
    from benchmarks import (bench_gateway, bench_kernels, bench_multihost,
                            bench_outofcore, bench_partition, bench_query,
                            bench_recovery, bench_scaling, bench_streaming,
                            bench_svi, bench_vmp)
    mods = {"vmp": bench_vmp, "scaling": bench_scaling,
            "partition": bench_partition, "kernels": bench_kernels,
            "svi": bench_svi, "outofcore": bench_outofcore,
            "query": bench_query, "streaming": bench_streaming,
            "recovery": bench_recovery, "multihost": bench_multihost,
            "gateway": bench_gateway}
    args = sys.argv[1:]
    json_mode = "--json" in args
    picks = [a for a in args if a in mods] or list(mods)

    import jax

    from repro import compile_cache
    from repro.kernels.ops import _backend
    compile_cache.enable()
    backend = _backend()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count()}

    print("name,us_per_call,derived")
    for p in picks:
        rows: list[dict] = []

        def report(name: str, us_per_call: float, derived: str = "",
                   **extra) -> None:
            print(f"{name},{us_per_call:.2f},{derived}")
            rows.append({"name": name, "us_per_call": round(us_per_call, 2),
                         "derived": derived, **extra})

        mods[p].run(report)
        if json_mode:
            path = f"BENCH_{p}.json"
            with open(path, "w") as fh:
                json.dump({"module": p, "backend": backend, "device": device,
                           "rows": rows}, fh, indent=1)
            print(f"# wrote {path}", file=sys.stderr)


if __name__ == "__main__":
    main()
