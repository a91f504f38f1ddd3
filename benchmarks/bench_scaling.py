"""Paper Figures 18 (scale-up: data size) and 19 (scale-out: cluster size).

Scale-up runs LDA per-iteration time against growing corpora in-process.
Scale-out launches subprocesses with 1/2/4/8 fake CPU devices (device count
locks at first jax init; ``JAX_PLATFORMS=cpu``, so a child never contends
for a chip the parent holds) and measures the inferspark-strategy step time.
A child that fails fails the benchmark.
"""

from __future__ import annotations

import os
import subprocess
import sys
import time

from repro.core import models
from repro.data import SyntheticCorpus

_SCALE_OUT_SNIPPET = r"""
import os, sys, time
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={sys.argv[1]}"
import jax, numpy as np
from repro.compat import make_mesh
from repro.core import models
from repro.core.partition import ShardingPlan
from repro.data import SyntheticCorpus

n_dev = int(sys.argv[1])
corpus = SyntheticCorpus(n_docs=600, vocab=2000, n_topics=16,
                         mean_len=120, seed=0).generate()
m = models.make("lda", alpha=0.1, beta=0.05, K=16, V=2000)
m["x"].observe(corpus["tokens"], segment_ids=corpus["doc_ids"])
mesh = make_mesh((n_dev,), ("data",))
plan = ShardingPlan(mesh, ("data",), "inferspark")
m.infer(steps=2, sharding=plan)          # warmup + compile
t0 = time.time()
m.infer(steps=10, sharding=plan)
print("PER_ITER_US", (time.time() - t0) / 10 * 1e6)
"""


def run(report):
    # Figure 18: scale-up.  The 2400/4800-doc points (4-8x the seed sweep's
    # max) exist because the fused zstats substep dropped the (N, K) arrays
    # from the step's working set — see docs/performance.md.
    for n_docs in (150, 300, 600, 2400, 4800):
        corpus = SyntheticCorpus(n_docs=n_docs, vocab=2000, n_topics=16,
                                 mean_len=120, seed=0).generate()
        m = models.make("lda", alpha=0.1, beta=0.05, K=16, V=2000)
        m["x"].observe(corpus["tokens"], segment_ids=corpus["doc_ids"])
        m.infer(steps=2)
        t0 = time.time()
        m.infer(steps=8)
        dt = (time.time() - t0) / 8
        report(f"vmp_scaleup_{len(corpus['tokens'])}tok", dt * 1e6,
               f"docs={n_docs};words_per_s={len(corpus['tokens'])/dt:.0f}")

    # Figure 19: scale-out (subprocesses, fake devices)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=os.path.join(root, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    for n_dev in (1, 2, 4, 8):
        out = subprocess.run(
            [sys.executable, "-c", _SCALE_OUT_SNIPPET, str(n_dev)],
            capture_output=True, text=True, timeout=900, env=env)
        line = [l for l in out.stdout.splitlines()
                if l.startswith("PER_ITER_US")]
        if out.returncode != 0 or not line:
            raise RuntimeError(f"scale-out child ({n_dev} devices) failed "
                               f"with rc {out.returncode}:\n{out.stderr}")
        report(f"vmp_scaleout_{n_dev}dev", float(line[0].split()[1]),
               "strategy=inferspark;note=fake_cpu_devices_1core")
