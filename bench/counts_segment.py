"""The work a segment-latent token plate needs (SLDA: one topic a
sentence), from shapes alone, counted as ``bench/counts.py`` counts
LDA's: the work of the algorithm, no one-hot multiply-adds, an exp, log
or digamma as one operation, real (unpadded) tokens and sentences.

Shapes: ``n`` tokens, ``s`` sentences, ``k`` topics, ``v`` vocabulary
words, ``b`` document rows.  A token is two int32 indices (its word and
its sentence); a sentence one (its document row).  The ``(s, k)``
sentence logits are an intermediate, like LDA's responsibilities, and
move no counted bytes.
"""

from bench import counts

TOKEN_BYTES = 2 * 4          # word id + sentence, int32 each
SENT_BYTES = 4               # document row, int32
# per token and topic: add the word's message into its sentence's logits
# (1), and add its sentence's responsibility into the word's statistics (1)
TOKEN_OPS_PER_TOPIC = 2
# per sentence and topic: add the document's message (1), softmax's max,
# subtract, exp, sum and divide (5), and the document statistics scatter (1)
SENT_OPS_PER_TOPIC = 7


def zstats(n: int, s: int, k: int, v: int, b: int) -> dict:
    """One segment token-plate pass (``kernels/ops.py:zstats`` with a
    ``zmap`` child): the index streams, one read of both concentration
    tables with their Dirichlet expectations, the sentence reduction,
    softmax and the two scatters, and one flush of both statistics
    tables."""
    tables = b * k + k * v
    flops = (TOKEN_OPS_PER_TOPIC * k * n + SENT_OPS_PER_TOPIC * k * s
             + counts.TABLE_OPS * tables + tables)
    nbytes = TOKEN_BYTES * n + SENT_BYTES * s + 2 * counts.F32 * tables
    return {"flops": float(flops), "bytes": float(nbytes)}


def svi_step(n: int, s: int, k: int, v: int, b: int) -> dict:
    """One SVI step: the segment plate plus what ``counts.svi_step`` adds
    to LDA's plate (row gather and write-back, Dirichlet ELBO terms, the
    natural-gradient blend), which does not depend on the plate."""
    rest = {kk: counts.svi_step(0, k, v, b)[kk] - counts.zstats(0, k, v, b)[kk]
            for kk in ("flops", "bytes")}
    return counts.add(zstats(n, s, k, v, b), rest)


def local_scorer(n: int, s: int, k: int, v: int, b: int,
                 passes: int) -> dict:
    """``passes`` segment-plate passes over the same tokens (local
    iterations plus the final scoring pass)."""
    z = zstats(n, s, k, v, b)
    return {"flops": passes * z["flops"], "bytes": passes * z["bytes"]}
