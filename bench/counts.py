"""The work each layer needs, from shapes alone.

Counts are of what the algorithm needs, not of how a kernel implements
it: the one-hot multiply-adds that the Pallas kernels use to gather and
scatter on the MXU are an implementation choice and are not counted, so a
count reads the same whatever computes the layer.  An exp, log or
digamma counts as one operation.  Tokens are real (unpadded) tokens.

Shapes: ``n`` tokens, ``k`` topics, ``v`` vocabulary words, ``b`` document
rows the call touches.  Tables and statistics are float32; a token is
two int32 indices (its word and its document row).
"""

F32 = 4
TOKEN_BYTES = 2 * 4          # word id + document row, int32 each

# per token and topic: gather-add of the two messages (1), softmax's max,
# subtract, exp, sum and divide (5), and the two statistics scatters (2)
TOKEN_OPS_PER_TOPIC = 8
# per table entry: digamma, and the subtraction of its row's digamma
TABLE_OPS = 2


def zstats(n: int, k: int, v: int, b: int) -> dict:
    """One token-plate pass (``kernels/ops.py:zstats``): the index streams,
    one read of both concentration tables (document rows ``b x k`` and
    topics ``k x v``) with their Dirichlet expectations, the per-token
    gather, softmax and scatter, and one flush of both statistics
    tables."""
    tables = b * k + k * v
    flops = TOKEN_OPS_PER_TOPIC * k * n + TABLE_OPS * tables + tables
    nbytes = TOKEN_BYTES * n + 2 * F32 * tables
    return {"flops": float(flops), "bytes": float(nbytes)}


def svi_step(n: int, k: int, v: int, b: int) -> dict:
    """One SVI step (``core/svi.py:make_svi_step``, one local pass): the
    token plate, the gather and write-back of the batch's document rows,
    the Dirichlet ELBO terms of both tables (lgamma, digamma and a
    product per entry) and the natural-gradient blend over the topic
    table (``phi <- (1 - rho) phi + rho (beta + scale * stats)``: read
    ``phi`` and the statistics, write ``phi``)."""
    z = zstats(n, k, v, b)
    kv, bk = k * v, b * k
    flops = z["flops"] + 4 * (kv + bk) + 5 * kv
    nbytes = z["bytes"] + 2 * F32 * bk + F32 * kv + 3 * F32 * kv
    return {"flops": float(flops), "bytes": float(nbytes)}


def local_scorer(n: int, k: int, v: int, b: int, passes: int) -> dict:
    """A frozen-globals local scorer (held-out ELBO, fold-in): ``passes``
    token-plate passes over the same tokens (local iterations plus the
    final scoring pass)."""
    z = zstats(n, k, v, b)
    return {"flops": passes * z["flops"], "bytes": passes * z["bytes"]}


def add(*counts: dict) -> dict:
    """Sum of several counts."""
    return {"flops": float(sum(c["flops"] for c in counts)),
            "bytes": float(sum(c["bytes"] for c in counts))}
