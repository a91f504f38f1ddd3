"""Multi-host distributed SVI: scaling curves across host topologies on the
out-of-core benchmark corpus, with per-host working-set accounting.

The corpus is bench_outofcore's largest single-host run (19200 docs /
~2.3M tokens, written shard by shard), so the multi-host numbers are
directly comparable to the single-host trajectory.  Each topology runs in
a child interpreter (jax locks its process/device topology at first init):

  ``single``    1 process, no partitioning — the baseline
  ``virtual2``  1 process, 2 virtual hosts over 2 fake CPU devices —
                partitioned batching, same SPMD program as the real thing
  ``2proc``     2 real ``jax.distributed`` processes (gloo CPU
                collectives), one device each — every host mmaps ONLY its
                owned shards

Per host we report us/step, tokens/s, and the working set the multi-host
design bounds: ``lengths.nbytes`` (global metadata, replicated) +
``peak_buffer_bytes`` (double-buffered batch host arrays) +
``owned_disk_bytes`` (the page-cache ceiling — only owned shards are ever
mapped).  The children run on CPU devices (``JAX_PLATFORMS=cpu``), so none
contends for a chip the parent holds; a child that fails fails the bench.
"""

from __future__ import annotations

import json
import os
import shutil
import socket
import subprocess
import sys
import tempfile
import time

from benchmarks.bench_outofcore import (RESIDENT_DOCS, SCALE, V, _chunk,
                                        _planted_phi)

N_STEPS = 30
BATCH = 256


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# ---------------------------------------------------------------------------
# child: one host of one topology
# ---------------------------------------------------------------------------

def _child(topo: str, pid: int, n_hosts: int, port: int, corpus_dir: str,
           out_path: str, steps: int) -> None:
    import jax

    from repro.compat import make_mesh
    from repro.core import models
    from repro.core.partition import ShardingPlan
    from repro.core.svi import SVI, SVIConfig
    from repro.data import HostAssignment, ShardedCorpus

    hosts = None
    if topo == "2proc":
        from repro.compat import distributed_initialize
        distributed_initialize(f"127.0.0.1:{port}", n_hosts, pid)
        hosts = HostAssignment(n_hosts, jax.process_index())
        corpus = ShardedCorpus.open(corpus_dir, hosts=hosts)
    else:
        corpus = ShardedCorpus.open(corpus_dir)
        if topo == "virtual2":
            hosts = HostAssignment(n_hosts, 0)
    plan = None
    if hosts is not None:
        mesh = make_mesh((jax.device_count(),), ("data",))
        plan = ShardingPlan(mesh, ("data",), "inferspark")
    cfg = SVIConfig(batch_size=BATCH, holdout_frac=0.0, pad_multiple=2048,
                    seed=0)
    svi = SVI(models.make("lda", alpha=0.1, beta=0.05, K=16, V=V), cfg,
              plan=plan, corpus=corpus, hosts=hosts)
    state, _ = svi.fit(steps=2)                  # compile + warm the caches
    t0 = time.time()
    state, _ = svi.fit(steps=steps, state=state)
    dt = time.time() - t0
    svi.close()
    tokens_per_step = corpus.n_tokens / svi.sampler.batches_per_epoch
    working_set = (corpus.lengths.nbytes + svi.sampler.peak_buffer_bytes
                   + corpus.owned_disk_bytes)
    with open(out_path, "w") as fh:
        json.dump({
            "topo": topo, "host": pid, "n_hosts": n_hosts,
            "us_per_step": dt / steps * 1e6,
            "tokens_per_s": tokens_per_step * steps / dt,
            "peak_buffer_bytes": int(svi.sampler.peak_buffer_bytes),
            "lengths_bytes": int(corpus.lengths.nbytes),
            "owned_disk_bytes": int(corpus.owned_disk_bytes),
            "owned_shards": int(len(corpus.owned_shards())),
            "n_shards": int(corpus.n_shards),
            "disk_bytes": int(corpus.disk_bytes),
            "working_set_bytes": int(working_set),
            "n_docs": int(corpus.n_docs), "n_tokens": int(corpus.n_tokens),
        }, fh)
    print("BENCH CHILD DONE", topo, pid)


def _spawn(topo: str, pid: int, n_hosts: int, port: int, corpus_dir: str,
           out_path: str) -> subprocess.Popen:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if topo == "virtual2":
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    else:
        env.pop("XLA_FLAGS", None)
    return subprocess.Popen(
        [sys.executable, "-m", "benchmarks.bench_multihost", "--child",
         topo, str(pid), str(n_hosts), str(port), corpus_dir, out_path,
         str(N_STEPS)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)


# ---------------------------------------------------------------------------
# parent: corpus + topology sweep
# ---------------------------------------------------------------------------

def run(report):
    phi_cdf = _planted_phi().cumsum(axis=1)
    tmp = tempfile.mkdtemp(prefix="bench_multihost_")
    try:
        from repro.data import ShardedCorpusWriter
        n_chunks, chunk_docs = SCALE * 2, RESIDENT_DOCS // 2
        w = ShardedCorpusWriter(os.path.join(tmp, "corpus"),
                                shard_tokens=1 << 17, vocab=V)
        for i in range(n_chunks):
            tokens, lengths = _chunk(phi_cdf, chunk_docs, chunk_seed=i + 1)
            w.add_docs(tokens, lengths)
        corpus = w.close()
        assert corpus.n_docs == SCALE * RESIDENT_DOCS
        report("multihost_corpus", 0.0,
               f"docs={corpus.n_docs};tokens={corpus.n_tokens};"
               f"shards={corpus.n_shards};"
               f"disk_mb={corpus.disk_bytes / 1e6:.1f}")

        results: dict[str, list[dict]] = {}
        for topo, n_hosts, n_procs in (("single", 1, 1), ("virtual2", 2, 1),
                                       ("2proc", 2, 2)):
            port = _free_port()
            outs = [os.path.join(tmp, f"{topo}.{p}.json")
                    for p in range(n_procs)]
            procs = [_spawn(topo, p, n_hosts, port,
                            os.path.join(tmp, "corpus"), outs[p])
                     for p in range(n_procs)]
            errs = []
            for p in procs:
                try:
                    _, err = p.communicate(timeout=1200)
                except subprocess.TimeoutExpired:
                    p.kill()
                    _, err = p.communicate()
                errs.append(err)
            if any(p.returncode != 0 for p in procs):
                raise RuntimeError(
                    f"multihost {topo} child failed (rc "
                    f"{[p.returncode for p in procs]}):\n"
                    + "\n".join(e or "" for e in errs))
            results[topo] = [json.load(open(o)) for o in outs]

        base = results["single"][0]["tokens_per_s"]
        for topo, rows in results.items():
            agg_tok = rows[0]["tokens_per_s"]   # global schedule: identical
            for r in rows:
                speedup = agg_tok / base
                report(
                    f"multihost_{topo}_host{r['host']}", r["us_per_step"],
                    f"tokens_per_s={r['tokens_per_s']:.0f};"
                    f"speedup_vs_single={speedup:.3f};"
                    f"working_set_mb={r['working_set_bytes'] / 1e6:.2f};"
                    f"owned_disk_mb={r['owned_disk_bytes'] / 1e6:.2f};"
                    f"owned_shards={r['owned_shards']}/{r['n_shards']};"
                    f"peak_buffer_mb={r['peak_buffer_bytes'] / 1e6:.2f}",
                    **{k: r[k] for k in
                       ("topo", "host", "n_hosts", "tokens_per_s",
                        "working_set_bytes", "owned_disk_bytes",
                        "peak_buffer_bytes", "lengths_bytes",
                        "owned_shards", "n_shards", "n_docs", "n_tokens")})

        # the design's working-set claim: a real multi-host host maps only
        # its owned shards — strictly less disk exposure than the baseline
        whole = results["single"][0]["owned_disk_bytes"]
        for r in results["2proc"]:
            assert r["owned_disk_bytes"] < whole, (
                f"host {r['host']} maps the whole corpus")
        covered = sum(r["owned_disk_bytes"] for r in results["2proc"])
        assert covered == whole, "owned shards do not partition the disk"
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    if len(sys.argv) >= 2 and sys.argv[1] == "--child":
        _, _, topo, pid, n_hosts, port, corpus_dir, out_path, steps = \
            sys.argv
        _child(topo, int(pid), int(n_hosts), int(port), corpus_dir,
               out_path, int(steps))
    else:
        run(lambda name, us, derived="", **_:
            print(f"{name},{us:.2f},{derived}"))
