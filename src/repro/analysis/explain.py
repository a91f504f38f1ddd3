"""Inference EXPLAIN plans: what will run, before anything runs.

Given ``(model, corpus metadata, config)``, :func:`explain_plan`
statically reproduces every decision the engines and kernels will make —
without tracing a single token or allocating a device buffer:

  - the **padded-shape signature** the jitted step will be traced at
    (for SVI, by replaying the real ``MinibatchSampler.batch_at(0)`` and
    the real ``slice_arrays`` padding — both pure numpy — so the
    predicted signature is the dict key ``SVI.step`` caches under,
    exactly);
  - the **kernel route** per latent (ref / fused / fused-streamed /
    fused-zmap, plus the streaming tile layout), computed by
    :func:`repro.kernels.ops.routing` — the one route decision, which
    ``ops.zstats`` dispatches on, so plan and execution cannot drift;
  - the **predicted HBM traffic** of the fused vs unfused token-plate
    substep, from the ``docs/performance.md`` model;
  - the **per-host partition** (owned shards/docs/bytes per host) when a
    sharded corpus and ``n_hosts`` are given;
  - the estimated per-step **working set** vs the corpus size.

CLI::

    PYTHONPATH=src python -m repro.analysis.explain --model lda \\
        --docs 2000 --vocab 10000 --topics 64 --engine svi --backend pallas

"why is large-vocab SLDA slow" is a plan row, not a profiling session.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Optional

import numpy as np

from repro.analysis.diagnostics import Diagnostic

__all__ = ["explain_plan", "Plan", "KernelRoute", "synthesize_model"]


class _ShapeOnly:
    """Stand-in carrying just ``.shape``/``.dtype`` — what ``routing``
    (and nothing else) reads; guarantees no array ever materializes."""
    __slots__ = ("shape", "dtype")

    def __init__(self, shape, dtype="float32"):
        self.shape = tuple(shape)
        self.dtype = dtype


@dataclasses.dataclass
class KernelRoute:
    """One plan row: the kernel decision for one latent's zstats call."""
    latent: str
    prior_dir: str
    n_latent: int                   # latent instances the step sees (padded)
    n_tokens: int                   # observed child instances (padded)
    k: int
    table_shapes: dict              # dirichlet name -> (g, k) the step sees
    path: str                       # ref | fused | fused-streamed | fused-zmap
    backend: str
    table_dtype: str
    target: object                  # streamed table: None | "prior" | child i
    tile: int
    n_tiles: int
    block_tokens: int
    table_bytes: int                # padded resident footprint vs budget
    budget: int
    reason: str
    hbm_unfused: int                # predicted bytes/step, unfused chain
    hbm_fused: int                  # predicted bytes/step, fused kernel

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class Plan:
    """The full EXPLAIN plan; ``render()`` for humans, ``to_json()`` for
    machines."""
    model: str
    engine: str                     # "vmp" (full batch) | "svi" | "gibbs"
    backend: str
    tables: str                     # zstats table mode the step uses
    diagnostics: list               # validate findings (errors stop the plan)
    caps: Optional[dict]            # padded-shape signature (sliced axes)
    signature: Optional[tuple]      # the SVI step-cache key, exactly
    routes: list                    # KernelRoute per latent
    hosts: Optional[list]           # per-host partition summary dicts
    working_set: Optional[dict]     # bytes: batch / tables / corpus
    notes: list

    def to_json(self, indent: int = 1) -> str:
        d = dataclasses.asdict(self)
        d["diagnostics"] = [dataclasses.asdict(x) for x in self.diagnostics]

        def _py(o):
            if isinstance(o, (np.integer,)):
                return int(o)
            if isinstance(o, (np.floating,)):
                return float(o)
            raise TypeError(f"not JSON-serializable: {o!r}")
        return json.dumps(d, indent=indent, default=_py)

    def render(self) -> str:
        out = [f"EXPLAIN {self.model} · engine={self.engine} "
               f"backend={self.backend} tables={self.tables}"]
        errs = [d for d in self.diagnostics if d.severity == "error"]
        for d in self.diagnostics:
            out.append(f"  {d}")
        if errs:
            out.append("  plan aborted: fix the errors above")
            return "\n".join(out)
        if self.caps:
            out.append("  step signature (padded-shape caps):")
            for name, cap in sorted(self.caps.items()):
                out.append(f"    {name:<12} {cap}")
        for r in self.routes:
            out.append(f"  latent {r.latent} (prior {r.prior_dir}): "
                       f"route={r.path}")
            tabs = ", ".join(f"{n}:{s[0]}x{s[1]}"
                             for n, s in r.table_shapes.items())
            out.append(f"    instances={r.n_latent} tokens={r.n_tokens} "
                       f"K={r.k} tables[{r.table_dtype}] {tabs}")
            out.append(f"    resident footprint {_fmt(r.table_bytes)} vs "
                       f"budget {_fmt(r.budget)}"
                       + (f"; streaming target={r.target!r} "
                          f"tile={r.tile} n_tiles={r.n_tiles}"
                          if r.path == "fused-streamed" else ""))
            out.append(f"    {r.reason}")
            out.append(f"    HBM/step: fused {_fmt(r.hbm_fused)} vs "
                       f"unfused {_fmt(r.hbm_unfused)} "
                       f"({r.hbm_unfused / max(r.hbm_fused, 1):.1f}x)")
        if self.hosts:
            out.append("  host partition:")
            for h in self.hosts:
                out.append(f"    host {h['host']}: {h['shards']} shards, "
                           f"{h['docs']} docs, {_fmt(h['bytes'])}")
        if self.working_set:
            w = self.working_set
            out.append(f"  working set/step: batch {_fmt(w['batch_bytes'])} "
                       f"+ tables {_fmt(w['table_bytes'])}"
                       + (f" (corpus {_fmt(w['corpus_bytes'])}, "
                          f"{w['fraction']:.3f}x)"
                          if w.get("corpus_bytes") else ""))
        for n in self.notes:
            out.append(f"  note: {n}")
        return "\n".join(out)


def _fmt(b: int) -> str:
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(b) < 1024 or unit == "GiB":
            return f"{b:.1f}{unit}" if unit != "B" else f"{b}B"
        b /= 1024
    return f"{b}B"                                     # pragma: no cover


# ---------------------------------------------------------------------------
# caps prediction: replay the real sampler + the real slicer, in numpy
# ---------------------------------------------------------------------------

def _svi_caps(program, cfg, corpus=None):
    """The exact cap signature ``SVI.step(0)`` will trace at: the same
    holdout split, the same ``batch_at(0)``, the same ``slice_arrays``
    padding (``slice_sharded`` over a sharded ``corpus``) — all the actual
    code, none of it traced."""
    from repro.core.compiler import slice_arrays
    from repro.data.pipeline import MinibatchSampler, holdout_split
    from repro.data.store import slice_sharded

    n_groups = program.meta["pstar_size"]
    if cfg.holdout_frac > 0:
        train, _ = holdout_split(n_groups, cfg.holdout_frac, cfg.seed)
    else:
        train = np.arange(n_groups, dtype=np.int64)
    batch_size = min(cfg.batch_size, len(train))
    sampler = MinibatchSampler(groups=train, batch_size=batch_size,
                               seed=cfg.seed, shuffle=cfg.shuffle)

    def caps_fn(name, n):
        m = cfg.pad_multiple
        return n if not m else -(-max(n, 1) // m) * m

    if corpus is None:
        arrays, dirs, caps, n_tokens = slice_arrays(
            program, sampler.batch_at(0), caps_fn)
    else:
        arrays, dirs, caps, n_tokens = slice_sharded(
            program, corpus, sampler.batch_at(0), caps_fn)
    batch_bytes = sum(a.nbytes for d in arrays.values()
                      for a in d.values() if a is not None)
    batch_bytes += sum(a.nbytes for d in dirs.values() for a in d.values())
    return caps, batch_bytes, n_tokens


def _full_caps(program):
    """Full-batch extents: the static shapes a VMP/Gibbs step traces at."""
    caps = {}
    for spec in program.latents:
        caps[spec.name] = spec.n
        for f in spec.children:
            caps[f.x_name] = len(f.values)
    for s in program.statics:
        caps[s.x_name] = len(s.values)
    batch_bytes = sum(4 * caps[k] for k in caps)   # int32 index streams
    return caps, batch_bytes


# ---------------------------------------------------------------------------
# per-latent kernel routes
# ---------------------------------------------------------------------------

def _routes(program, caps, sliced, backend, tables, elog_dtype):
    """One :class:`KernelRoute` per latent, from shape stand-ins through
    the real :func:`repro.kernels.ops.routing` planner."""
    from repro.kernels.ops import ZChild, routing

    dtype = str(elog_dtype) if elog_dtype else "float32"
    _MARK = object()                  # non-None stand-in for base/zmap
    out = []
    for spec in program.latents:
        def _g(dname):
            d = program.dirichlets[dname]
            if sliced and d.group_rows is not None:
                return caps[dname]
            return d.g
        k = program.dirichlets[spec.prior_dir].k
        nz = caps[spec.name] if sliced else spec.n
        prior_tab = _ShapeOnly((_g(spec.prior_dir), k), dtype)
        shapes = {spec.prior_dir: prior_tab.shape}
        children, n_tok, zmap_tok = [], 0, 0
        for f in spec.children:
            d = program.dirichlets[f.dir_name]
            tab = _ShapeOnly((_g(f.dir_name), d.k), dtype)
            shapes[f.dir_name] = tab.shape
            nt = caps[f.x_name] if sliced else len(f.values)
            n_tok += nt
            if f.zmap is not None:
                zmap_tok += nt
            children.append(ZChild(
                elog=tab, values=None, stride=f.stride if f.stride else 1,
                zmap=_MARK if f.zmap is not None else None,
                base=_MARK if f.base is not None else None))
        n_tok = n_tok or nz           # childless latent: one row per instance
        r = routing(prior_tab, None, tuple(children), tables=tables,
                    backend=backend, n_latent=nz)
        words = sum(g * kk for g, kk in shapes.values())
        if zmap_tok:
            unfused = 4 * (5 * n_tok * k + 4 * nz * k + 2 * words)
            fused = 4 * (4 * n_tok + 4 * nz * k + 2 * words)
        else:
            unfused = 4 * (7 * n_tok * k + 2 * words)
            fused = 4 * ((3 if r.path == "fused-streamed" else 2) * n_tok
                         + 2 * words)
        out.append(KernelRoute(
            latent=spec.name, prior_dir=spec.prior_dir, n_latent=int(nz),
            n_tokens=int(n_tok), k=int(k), table_shapes=shapes,
            path=r.path, backend=r.backend, table_dtype=r.table_dtype,
            target=r.target, tile=r.tile, n_tiles=r.n_tiles,
            block_tokens=r.block_tokens, table_bytes=r.table_bytes,
            budget=r.budget, reason=r.reason,
            hbm_unfused=int(unfused), hbm_fused=int(fused)))
    return out


# ---------------------------------------------------------------------------
# the plan
# ---------------------------------------------------------------------------

def explain_plan(model, config=None, *, corpus=None, backend=None,
                 n_hosts: Optional[int] = None) -> Plan:
    """Build the EXPLAIN plan for ``model`` under ``config``.

    ``model`` — a ``dsl.Model`` with observations bound (compile is pure
    numpy).  ``config`` — ``SVIConfig`` (minibatch plan), ``EngineConfig``
    (engine chosen by its ``backend`` field), or ``None`` (full-batch
    VMP).  ``corpus`` — optional ``ShardedCorpus`` for working-set and
    host-partition context; with a model that observes nothing, the plan
    is of SVI over that corpus (the program ``SVI(model, config,
    corpus=corpus)`` builds with ``sharded_template``, its first batch
    sliced by ``slice_sharded``, a sentence latent's axis in the notes).
    ``backend`` — plan for a specific kernel
    backend (``"pallas"`` to plan for TPU from anywhere); default is this
    process's dispatch answer.  ``n_hosts`` — include the multi-host
    partition summary.
    """
    from repro.analysis.validate import validate_model
    from repro.core.svi import SVIConfig
    from repro.kernels.ops import _backend

    engine, svi_cfg, elog_dtype, notes = "vmp", None, None, []
    if isinstance(config, SVIConfig):
        engine, svi_cfg, elog_dtype = "svi", config, config.elog_dtype
    elif config is not None:                # EngineConfig (duck-typed)
        engine = getattr(config, "backend", "vmp")
        elog_dtype = getattr(config, "elog_dtype", None)
        if engine == "svi":
            from repro.core.engine import _svi_config
            svi_cfg = _svi_config(config, full_batch=False, n_groups=0)
        elif engine == "gibbs":
            notes.append("gibbs runs full-batch sweeps; routes below are "
                         "the fold-in scorer's (zstats) view")

    b = backend if backend is not None else _backend()
    sharded = corpus is not None and not getattr(model, "observations", {})
    if sharded and svi_cfg is None:
        raise ValueError("a sharded corpus is trained by SVI; pass an "
                         "SVIConfig (or an EngineConfig with backend='svi')")
    diags = validate_model(model)
    if sharded:                     # the corpus is what the model observes
        diags = [d for d in diags if d.code != "no-observed"]
    name = getattr(getattr(model, "net", model), "name", "?")
    plan = Plan(model=name, engine=engine, backend=b, tables="alpha",
                diagnostics=diags, caps=None, signature=None, routes=[],
                hosts=None, working_set=None, notes=notes)
    if any(d.severity == "error" for d in diags):
        return plan

    if sharded:
        from repro.data.store import sharded_template
        program = sharded_template(model, corpus)
    else:
        program = model.compile()
    if svi_cfg is not None:
        if program.meta.get("pstar") is None:
            plan.notes.append("model has no '?' partition plate; SVI "
                              "unavailable — planning full batch instead")
            svi_cfg = None
    if svi_cfg is not None:
        caps, batch_bytes, _ = _svi_caps(program, svi_cfg,
                                         corpus if sharded else None)
        plan.caps = dict(caps)
        plan.signature = tuple(sorted(caps.items()))
        plan.routes = _routes(program, caps, True, b, "alpha", elog_dtype)
        for spec in program.latents:
            if any(f.zmap is not None for f in spec.children):
                plate = program.net.rvs[spec.name].plate.name
                plan.notes.append(
                    f"sentence axis: latent {spec.name} on plate {plate}, "
                    f"{spec.n} instances in all, {caps[spec.name]} a step "
                    f"(padded); each token's messages are summed into its "
                    f"instance before the softmax")
    else:
        caps, batch_bytes = _full_caps(program)
        plan.caps = dict(caps)
        plan.signature = tuple(sorted(caps.items()))
        plan.routes = _routes(program, caps, False, b, "alpha", elog_dtype)

    word = 2 if str(elog_dtype or "") == "bfloat16" else 4
    table_bytes = sum(word * d.g * d.k for d in program.dirichlets.values())
    ws = {"batch_bytes": int(batch_bytes), "table_bytes": int(table_bytes)}
    if corpus is not None:
        cb = int(getattr(corpus, "disk_bytes", 0) or 0)
        if cb:
            ws["corpus_bytes"] = cb
            ws["fraction"] = (batch_bytes + table_bytes) / cb
    plan.working_set = ws

    if n_hosts and corpus is not None:
        from repro.data.store import doc_ownership, shard_ownership
        manifest = corpus.manifest
        owner = shard_ownership(len(manifest["shards"]), n_hosts)
        downer = doc_ownership(manifest, n_hosts)
        plan.hosts = []
        for h in range(n_hosts):
            sids = np.flatnonzero(owner == h)
            ndocs = int((downer == h).sum())
            nbytes = sum(int(manifest["shards"][int(s)].get("n_tokens", 0))
                         * 4 for s in sids)
            plan.hosts.append({"host": h, "shards": int(len(sids)),
                               "docs": ndocs, "bytes": int(nbytes)})
    return plan


# ---------------------------------------------------------------------------
# CLI: synthesize a zoo model from shape knobs and print its plan
# ---------------------------------------------------------------------------

def synthesize_model(name: str, *, docs: int, vocab: int, topics: int,
                     mean_len: int = 100, sents_per_doc: int = 8,
                     seed: int = 0):
    """A zoo model with synthetic observations at the given shapes —
    numpy only (token *values* never influence a plan, only extents do)."""
    from repro.core import models

    rng = np.random.default_rng(seed)
    n_tok = docs * mean_len
    toks = rng.integers(0, vocab, n_tok).astype(np.int32)
    doc_of_tok = np.repeat(np.arange(docs, dtype=np.int32), mean_len)
    if name in ("lda", "dcmlda"):
        m = models.make(name, alpha=0.1, beta=0.05, K=topics, V=vocab)
        m["x"].observe(toks, segment_ids=doc_of_tok)
    elif name == "slda":
        n_sents = docs * sents_per_doc
        per_sent = max(mean_len // sents_per_doc, 1)
        sent_of_tok = np.repeat(np.arange(n_sents, dtype=np.int32), per_sent)
        toks = rng.integers(0, vocab, len(sent_of_tok)).astype(np.int32)
        doc_of_sent = np.repeat(np.arange(docs, dtype=np.int32),
                                sents_per_doc)
        m = models.make("slda", alpha=0.1, beta=0.05, K=topics, V=vocab)
        m["x"].observe(toks, segment_ids=sent_of_tok)
        m.bind("sents", doc_of_sent)
    elif name == "naive_bayes":
        m = models.make("naive_bayes", alpha=0.1, beta=0.05, C=topics,
                        V=vocab)
        m["x"].observe(toks, segment_ids=doc_of_tok)
    elif name == "two_coins":
        m = models.make("two_coins", alpha=1.0, beta=1.0)
        m["x"].observe(rng.integers(0, 2, docs).astype(np.int32))
    else:
        raise ValueError(f"unknown zoo model {name!r}")
    return m


def _main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.explain",
        description="Static inference EXPLAIN plan (no tracing, no device)")
    ap.add_argument("--model", default="lda",
                    help="zoo model: lda|slda|dcmlda|naive_bayes|two_coins")
    ap.add_argument("--docs", type=int, default=2000)
    ap.add_argument("--vocab", type=int, default=10000)
    ap.add_argument("--topics", type=int, default=64)
    ap.add_argument("--mean-len", type=int, default=100)
    ap.add_argument("--engine", default="svi", choices=["vmp", "svi"])
    ap.add_argument("--batch-docs", type=int, default=64)
    ap.add_argument("--pad-multiple", type=int, default=256)
    ap.add_argument("--elog-dtype", default=None,
                    help="e.g. bfloat16 for narrow tables")
    ap.add_argument("--backend", default=None,
                    help="plan for: pallas|pallas_interpret|ref "
                         "(default: this process's dispatch)")
    ap.add_argument("--corpus-dir", default=None,
                    help="ShardedCorpus directory: plan against its real "
                         "manifest/lengths instead of --docs/--mean-len")
    ap.add_argument("--hosts", type=int, default=None,
                    help="include the n-host partition summary")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)

    corpus = None
    if args.corpus_dir:
        from repro.core import models
        from repro.data.store import ShardedCorpus
        corpus = ShardedCorpus.open(args.corpus_dir)
        # unobserved: the plan is of SVI over the corpus itself
        m = models.make(args.model, alpha=0.1, beta=0.05, K=args.topics,
                        V=int(corpus.vocab))
    else:
        m = synthesize_model(args.model, docs=args.docs, vocab=args.vocab,
                             topics=args.topics, mean_len=args.mean_len)

    cfg = None
    if args.engine == "svi":
        from repro.core.svi import SVIConfig
        cfg = SVIConfig(batch_size=args.batch_docs,
                        pad_multiple=args.pad_multiple,
                        elog_dtype=args.elog_dtype)
    plan = explain_plan(m, cfg, corpus=corpus, backend=args.backend,
                        n_hosts=args.hosts)
    print(plan.to_json() if args.json else plan.render())
    return 1 if any(d.severity == "error" for d in plan.diagnostics) else 0


if __name__ == "__main__":          # pragma: no cover
    raise SystemExit(_main())
