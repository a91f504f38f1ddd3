"""The ``train_plan`` driver on four virtual CPU devices: the four-chip
PubMed cell's plan at a small size passes sound, and fails with the
psum of the global statistics left out.  JAX fixes its device count when
it starts, so the cells run in a child process."""

import json
import os
import subprocess
import sys

from bench.tests.conftest import ROOT

CHILD = r"""
import contextlib, json, time
import jax
from bench import faults_plan, harness
with open("bench/configs/lda-pubmed.json") as f:
    cfg = json.load(f)
cfg.update(K=8, V=300, D=160, N=3200, mean_doc_tokens=20,
           batch_docs_per_chip=8, holdout_docs=32, holdout_every=3,
           source_tokens=10000)
with open("bench/traffic/svi-plan.json") as f:
    tr = json.load(f)
devs = jax.devices()
assert len(devs) == 4, devs
lims = harness.limits("train.lda-pubmed.4chip")
for mode in ("sound", "stats_psum_left_out"):
    plant = (faults_plan.PLAN[mode] if mode != "sound"
             else contextlib.nullcontext)
    with plant():
        out = harness.run_cell("train.lda-pubmed.4chip", 2**31 + 3, 0.5,
                               False, devs, time.perf_counter(), cfg=cfg,
                               traffic_mix=tr, lims=lims)
    print(json.dumps({"mode": mode, "correct": out["correct"],
                      "count": out["device"]["count"],
                      "attempted": out["attempted"],
                      "checks": out["checks"]}), flush=True)
"""


def test_four_devices_sound_and_without_the_psum():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=f"{ROOT}:{ROOT / 'src'}")
    r = subprocess.run([sys.executable, "-c", CHILD], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    rows = {d["mode"]: d for d in map(json.loads,
                                       r.stdout.strip().splitlines())}
    assert rows["sound"]["correct"], rows["sound"]["checks"]
    assert rows["sound"]["count"] == 4 and rows["sound"]["attempted"] >= 3
    assert not rows["stats_psum_left_out"]["correct"], rows
    assert "EXPLAIN route of z" in r.stderr
