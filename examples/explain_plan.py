"""EXPLAIN two models side by side: why large-vocab SLDA routes differently.

Plans — no tracing, no device work — for the same SVI configuration over:

  - LDA at a moderate vocabulary: the phi table exceeds the VMEM budget,
    so the fused kernel streams it tile-by-tile (route ``fused-streamed``);
  - SLDA at a large vocabulary: the segment latent (one topic per
    sentence shared by its tokens) needs the two-phase zmap kernel, whose
    tables + (n_sents, K) logits blow the VMEM budget — route ``ref``,
    the chunked oracle.

Same budget, different structure, different kernel.  The plan says so
before the first step compiles.  The ``ref`` route of a segment latent at
a large vocabulary is measured on the chip: the benchmark cell
``train.slda-nytimes`` runs SLDA at V=102,660 and K=256 over a sharded
corpus, on the route this plan predicts (``bench/train_plan.py`` prints
it)::

    PYTHONPATH=src python examples/explain_plan.py [--docs 2000] [--json]
"""

import argparse

from repro.analysis.explain import explain_plan, synthesize_model
from repro.core.svi import SVIConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--docs", type=int, default=2000)
    ap.add_argument("--topics", type=int, default=64)
    ap.add_argument("--batch-docs", type=int, default=256)
    ap.add_argument("--backend", default="pallas",
                    help="plan for: pallas (TPU) | pallas_interpret | ref")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args()

    cfg = SVIConfig(batch_size=args.batch_docs, pad_multiple=256)
    cases = [
        ("lda", dict(docs=args.docs, vocab=10_000, topics=args.topics,
                     mean_len=100)),
        ("slda", dict(docs=args.docs, vocab=60_000, topics=32,
                      mean_len=200, sents_per_doc=20)),
    ]
    for name, knobs in cases:
        plan = explain_plan(synthesize_model(name, **knobs), cfg,
                            backend=args.backend)
        print(plan.to_json() if args.json else plan.render())
        print()

    routes = {name: explain_plan(synthesize_model(name, **knobs), cfg,
                                 backend=args.backend).routes[0]
              for name, knobs in cases}
    lda_r, slda_r = routes["lda"], routes["slda"]
    print(f"summary: lda routes {lda_r.path} "
          f"({lda_r.table_bytes / 2**20:.1f}MiB resident vs "
          f"{lda_r.budget / 2**20:.0f}MiB budget) while slda routes "
          f"{slda_r.path} ({slda_r.table_bytes / 2**20:.1f}MiB); SLDA's "
          f"ref route at the NYTimes widths is measured on the chip in the "
          f"benchmark cell train.slda-nytimes")


if __name__ == "__main__":
    main()
