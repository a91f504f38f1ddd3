"""End-to-end driver at the paper's experimental scale (Table 3): distributed
LDA over a ~0.5M-word synthetic corpus, 50 VMP iterations, checkpoint every
10 (the paper's own setting), with topic-recovery scoring at the end.

    PYTHONPATH=src python examples/lda_topics.py [--words 500000] [--topics 16]

On a TPU pod the same script runs with ``--devices N`` sharding tokens and
per-document posteriors across the mesh (the InferSpark partitioning).
"""

import argparse
import os
import shutil
import time

from repro.core import models
from repro.core.partition import ShardingPlan
from repro.data import SyntheticCorpus


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--words", type=int, default=500_000)
    ap.add_argument("--topics", type=int, default=16)
    ap.add_argument("--vocab", type=int, default=9040)   # paper's LDA vocab
    ap.add_argument("--iters", type=int, default=50)
    ap.add_argument("--engine", default="vmp", choices=["vmp", "svi", "gibbs"],
                    help="inference backend (full-batch VMP, streaming "
                         "minibatch SVI, or Gibbs sampling)")
    ap.add_argument("--batch-docs", type=int, default=256,
                    help="svi: documents per minibatch")
    ap.add_argument("--holdout", type=float, default=0.0,
                    help="fraction of docs held out for per-token ELBO")
    ap.add_argument("--distributed", action="store_true",
                    help="shard over all local jax devices")
    ap.add_argument("--corpus-dir", default=None,
                    help="out-of-core mode (svi engine only): directory of "
                         "a sharded corpus store; written from the "
                         "synthetic corpus on first use, then minibatches "
                         "stream from its shards (docs/data_pipeline.md)")
    ap.add_argument("--ckpt", default="/tmp/inferspark_lda_ck")
    ap.add_argument("--save-posterior", default=None, metavar="DIR",
                    help="freeze the fitted posterior into a servable "
                         "artifact at DIR (docs/query_serving.md); "
                         "query it with examples/query_topics.py")
    args = ap.parse_args()
    from repro import compile_cache
    compile_cache.enable()

    n_docs = max(10, args.words // 120)
    print(f"[lda] generating ~{args.words} words over {n_docs} docs ...")
    corpus = SyntheticCorpus(n_docs=n_docs, vocab=args.vocab,
                             n_topics=args.topics, mean_len=120,
                             seed=0).generate()
    n = len(corpus["tokens"])
    print(f"[lda] corpus: {n} tokens, vocab {args.vocab}, "
          f"{args.topics} topics")

    store = None
    if args.corpus_dir is not None:
        if args.engine != "svi":
            ap.error("--corpus-dir needs --engine svi (the streaming "
                     "engine is the out-of-core one)")
        from repro.data import ShardedCorpus, write_sharded_corpus
        if os.path.exists(os.path.join(args.corpus_dir, "manifest.json")):
            store = ShardedCorpus.open(args.corpus_dir)
            if (store.n_tokens != n or store.n_docs != n_docs
                    or store.vocab != args.vocab):
                ap.error(f"existing store at {args.corpus_dir} "
                         f"({store.n_docs} docs / {store.n_tokens} tokens / "
                         f"vocab {store.vocab}) does not match the requested "
                         f"corpus ({n_docs} docs / {n} tokens / vocab "
                         f"{args.vocab}); delete the directory or match "
                         f"the flags")
        else:
            store = write_sharded_corpus(corpus, args.corpus_dir,
                                         shard_tokens=1 << 18,
                                         vocab=args.vocab)
        print(f"[lda] sharded corpus at {args.corpus_dir}: "
              f"{store.n_shards} shards, {store.n_tokens} tokens, "
              f"{store.disk_bytes / 1e6:.1f} MB on disk")

    m = models.make("lda", alpha=0.1, beta=0.05, K=args.topics, V=args.vocab)
    if store is None:
        m["x"].observe(corpus["tokens"], segment_ids=corpus["doc_ids"])

    plan = None
    if args.distributed:
        import jax
        from repro.compat import make_mesh
        ndev = len(jax.devices())
        mesh = make_mesh((ndev,), ("data",))
        plan = ShardingPlan(mesh, ("data",), "inferspark")
        print(f"[lda] sharding over {ndev} devices (inferspark layout)")

    shutil.rmtree(args.ckpt, ignore_errors=True)
    t0 = time.time()

    if args.engine == "vmp" and args.holdout == 0 \
            and args.save_posterior is None:
        def progress(i, elbo):
            if i % 10 == 0:
                print(f"[lda] iter {i:3d}  ELBO {elbo:16.1f}  "
                      f"({(time.time()-t0):.1f}s)")
            return True

        # checkpoint every 10 iterations, the paper's section 5 setting
        m.infer(steps=args.iters, callback=progress,
                checkpoint_every=10, checkpoint_dir=args.ckpt, sharding=plan)
        dt = time.time() - t0
        print(f"[lda] {args.iters} iterations in {dt:.1f}s  "
              f"({n * args.iters / dt:.0f} words/s)  ELBO {m.lower_bound:.1f}")
        phi = m["phi"].get_result()
        est = phi / phi.sum(-1, keepdims=True)
    else:
        from repro.core import make_engine
        if args.ckpt != ap.get_default("ckpt"):
            print("[lda] note: --ckpt only applies to the default "
                  "--engine vmp path without --holdout")
        eng = make_engine(args.engine, steps=args.iters,
                          batch_size=args.batch_docs,
                          holdout_frac=args.holdout, sharding=plan,
                          corpus=store)
        result = eng.fit(m)
        dt = time.time() - t0
        print(f"[lda] {args.engine}: {args.iters} steps in {dt:.1f}s")
        if store is not None:
            print(f"[lda] out-of-core: read {store.bytes_read / 1e6:.1f} MB "
                  f"from {store.n_shards} shards "
                  f"({store.bytes_read / max(store.disk_bytes, 1):.1f}x "
                  f"corpus bytes over {args.iters} steps)")
        if result.heldout_trace:
            print(f"[lda] held-out per-token ELBO: "
                  f"{result.heldout_elbo:.4f}")
        est = result.topics("phi")
        if args.save_posterior:
            prog = None
            if store is not None:
                from repro.data.store import sharded_template
                prog = sharded_template(m, store)
            post = result.freeze(m, program=prog)
            post.save(args.save_posterior)
            print(f"[lda] posterior artifact at {args.save_posterior}: "
                  f"{sorted(post.posteriors)} "
                  f"(query it: PYTHONPATH=src python "
                  f"examples/query_topics.py {args.save_posterior})")

    # topic recovery vs the planted topics (TV distance, greedy matched)
    from repro.core import aligned_tv
    print(f"[lda] planted-topic recovery: mean TV distance "
          f"{aligned_tv(est, corpus['true_phi']):.3f} "
          f"(0=perfect, 1=disjoint)")
    if os.path.isdir(args.ckpt):
        print(f"[lda] checkpoints at {args.ckpt}: {os.listdir(args.ckpt)}")


if __name__ == "__main__":
    main()
