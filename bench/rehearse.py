"""Rehearse the cells without a chip: kernel routes, step signatures, and
a compile of each cell's largest program for a described v5e.

    JAX_PLATFORMS=cpu PYTHONPATH=src python bench/rehearse.py [--compile]

For each training cell: the route ``explain_plan`` gives the token plate,
and how many distinct step signatures the first 1,003 steps of a seed
hit, with the share of tokens that padding adds, two ways: as the cell
runs them (fixed batch order, every batch the same tokens) and as the
engine's defaults would (batches reshuffled every epoch over the
log-normal lengths, ``pad_multiple`` 256).  With ``--compile``: the SVI
step of each training cell and the serving cell's largest fold-in bucket
are compiled for a described v5e, and the bytes of ``memory_analysis()``
are printed.  Nothing runs on a device.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import pathlib
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def signatures(cfg, steps, seed, shuffled):
    """(distinct step signatures, padding share of tokens) over the first
    ``steps`` steps of ``seed``."""
    from bench import corpus as gen
    from bench import reference as ref
    from bench.train import Schedule, step_signature
    n, n_hold = cfg["D"], cfg["holdout_docs"]
    batch = cfg["batch_docs_per_chip"]
    rng = gen.rng_for(seed, 1)
    if shuffled:
        lengths = gen.lognormal_lengths(n, cfg["mean_doc_tokens"],
                                        cfg["doc_length_sigma"], rng)
        pad = 256
    else:
        train, hold = ref.holdout_split(n, n_hold, seed)
        lengths = gen.batched_lengths(n, cfg["mean_doc_tokens"],
                                      cfg["doc_length_sigma"], train, hold,
                                      batch, rng)
        pad = cfg["pad_multiple"]
    sched = Schedule(lengths, n_hold, batch, seed, shuffle=shuffled)
    sigs, real, padded = set(), 0, 0
    for t in range(steps):
        sig = step_signature(sched, t, pad)
        sigs.add(sig)
        real += sched.tokens(t)
        padded += sig[1]
    return len(sigs), 1 - real / padded


def shapes(tree, sharding):
    import jax
    return jax.tree_util.tree_map(
        lambda a: None if a is None else jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=sharding), tree,
        is_leaf=lambda a: a is None)


def compile_step(cfg, topo, seed):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from repro.core import models
    from repro.core.svi import SVI, SVIConfig, make_svi_step
    from repro.core.vmp import VMPState
    from repro.data import write_sharded_corpus
    from bench import corpus as gen
    from bench import reference as ref

    n, n_hold = cfg["D"], cfg["holdout_docs"]
    batch = cfg["batch_docs_per_chip"]
    rng = gen.rng_for(seed, 1)
    train, hold = ref.holdout_split(n, n_hold, seed)
    lengths = gen.batched_lengths(n, cfg["mean_doc_tokens"],
                                  cfg["doc_length_sigma"], train, hold,
                                  batch, rng)
    docs = gen.documents(lengths, cfg["K"], cfg["V"], cfg["alpha"],
                         cfg["zipf_s"], rng)
    with tempfile.TemporaryDirectory() as tmp:
        store = write_sharded_corpus(docs, tmp, vocab=cfg["V"])
        svi = SVI(models.make("lda", alpha=cfg["alpha"], beta=cfg["beta"],
                              K=cfg["K"], V=cfg["V"]),
                  SVIConfig(batch_size=batch,
                            pad_multiple=cfg["pad_multiple"],
                            holdout_frac=n_hold / n, shuffle=False,
                            prefetch=False, seed=seed),
                  corpus=store)
        hb, caps, _, _ = svi.sampler.host_batch_at(0)
        svi.close()
    step = make_svi_step(svi.program, caps)
    put = SingleDeviceSharding(topo.devices[0])
    state = VMPState({n: jax.ShapeDtypeStruct((d.g, d.k), jnp.float32,
                                              sharding=put)
                      for n, d in svi.program.dirichlets.items()},
                     jax.ShapeDtypeStruct((), jnp.int32, sharding=put))
    scal = jax.ShapeDtypeStruct((), jnp.float32, sharding=put)
    compiled = step.lower(state, shapes(hb, put), scal, scal).compile()
    return caps, compiled


def compile_foldin(cfg, tr, topo):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import SingleDeviceSharding
    from repro.core.svi import build_local_scorer
    from repro.query import Posterior
    from repro.query.foldin import FoldIn, FoldInConfig, _segment_arrays
    from bench.serve import buckets
    k, v = cfg["K"], cfg["V"]
    post = Posterior(posteriors={"phi": np.ones((k, v), np.float32)},
                     model="lda", params={"alpha": cfg["alpha"],
                                          "beta": cfg["beta"], "K": k,
                                          "V": v},
                     local=("theta",), observed=("x",), meta={})
    fold = FoldIn(post, FoldInConfig(local_iters=tr["foldin_local_iters"]))
    top = buckets(tr["max_batch_docs"], tr["max_doc_tokens"], 64)[-1]
    n_docs = -(-top // tr["max_doc_tokens"])
    lengths = np.full(n_docs, top // n_docs, np.int64)
    values = np.zeros(int(lengths.sum()), np.int32)
    program, arrays, dirs, caps, _, _, n_seg, _ = fold._prepare(
        values, None, lengths, None, None)
    seg = _segment_arrays(program, caps, dirs, n_seg)
    fn = build_local_scorer(program, caps, tr["foldin_local_iters"],
                            extras=True, n_seg=n_seg)
    one = SingleDeviceSharding(topo.devices[0])
    glob = {"phi": jax.ShapeDtypeStruct((k, v), jnp.float32, sharding=one)}
    compiled = fn.lower(glob, shapes(arrays, one), shapes(seg, one)).compile()
    return caps, compiled


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--compile", action="store_true")
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    from bench import harness
    bench = harness.benchmark()
    steps = 1003
    topo = None
    if args.compile:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        from repro.kernels import ops
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        # plan the kernels for the TPU although this process runs on CPU
        ops._backend_cached = lambda: "pallas"
    for w in bench["workloads"]:
        cfg, tr = harness.config(w["config"]), harness.traffic(w["traffic"])
        row = {"cell": w["name"]}
        if tr["driver"] == "train":
            import jax
            from repro.kernels import ops
            from repro.kernels.ref import ZChild
            rows = math.ceil(cfg["batch_docs_per_chip"] / cfg["pad_multiple"]
                             ) * cfg["pad_multiple"]
            route = ops.routing(
                jax.ShapeDtypeStruct((rows, cfg["K"]), "float32"), None,
                (ZChild(jax.ShapeDtypeStruct((cfg["K"], cfg["V"]),
                                             "float32"), None),),
                tables="alpha", backend="pallas", n_latent=1)
            row["route"] = {"path": route.path, "tiles": route.n_tiles,
                            "tile": route.tile, "reason": route.reason}
            for name, shuffled in (("cell", False), ("engine_default", True)):
                n, share = signatures(cfg, steps, args.seed, shuffled)
                row[name] = {"signatures": n, "padding_share": share}
            if args.compile:
                caps, c = compile_step(cfg, topo, args.seed)
                m = c.memory_analysis()
                row["compiled"] = {
                    "caps": caps, "kernels": c.as_text().count(
                        "tpu_custom_call"),
                    "argument_bytes": m.argument_size_in_bytes,
                    "output_bytes": m.output_size_in_bytes,
                    "temp_bytes": m.temp_size_in_bytes}
        elif args.compile:
            caps, c = compile_foldin(cfg, tr, topo)
            m = c.memory_analysis()
            row["compiled"] = {
                "caps": caps,
                "kernels": c.as_text().count("tpu_custom_call"),
                "argument_bytes": m.argument_size_in_bytes,
                "temp_bytes": m.temp_size_in_bytes}
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
