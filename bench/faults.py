"""Faults planted under the timed path, to show that ``correct`` catches
them: the tests plant them at a small size on the CPU, and
``calibrate.py`` at a cell's own size on the chip.  Each is a context
manager that patches the program and undoes the patch on exit."""

from __future__ import annotations

import contextlib

import numpy as np


@contextlib.contextmanager
def _patched(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


@contextlib.contextmanager
def train_state_unchanged():
    """``SVI.step`` computes its ELBO but returns the state it was given."""
    import jax.numpy as jnp
    from repro.core.svi import SVI
    from repro.core.vmp import VMPState
    step = SVI.step

    def stuck(self, t, state):
        copy = VMPState({n: jnp.copy(a) for n, a in state.posteriors.items()},
                        jnp.copy(state.step))
        _, elbo = step(self, t, copy)
        return VMPState(state.posteriors, state.step + 1), elbo

    with _patched(SVI, "step", stuck):
        yield


@contextlib.contextmanager
def train_half_batch():
    """Every minibatch loses half its documents; the engine scales the
    statistics by the documents it kept (the mean over the rest)."""
    from repro.core.svi import SVI
    load = SVI._load_groups

    def half(self, groups):
        return load(self, np.asarray(groups)[::2])

    with _patched(SVI, "_load_groups", half):
        yield


@contextlib.contextmanager
def train_token_altered():
    """The first token of every minibatch is read as the next word."""
    from repro.data import store

    sliced = store.slice_sharded

    def altered(template, corpus, groups, caps_fn=None):
        arrays, dirs, caps, n = sliced(template, corpus, groups, caps_fn)
        x = dict(arrays["x"])
        vals = x["values"].copy()
        vals[0] = (vals[0] + 1) % corpus.vocab
        x["values"] = vals
        return dict(arrays, x=x), dirs, caps, n

    with _patched(store, "slice_sharded", altered):
        yield


@contextlib.contextmanager
def serve_state_unchanged():
    """Fold-in's local passes leave the document rows at the prior."""
    from repro.query import foldin
    init = foldin.FoldIn.__init__

    def no_passes(self, posterior, config=None, model=None):
        init(self, posterior, config, model)
        self.cfg = foldin.FoldInConfig(
            local_iters=0, bucket=self.cfg.bucket, min_cap=self.cfg.min_cap,
            max_compiled=self.cfg.max_compiled)

    with _patched(foldin.FoldIn, "__init__", no_passes):
        yield


@contextlib.contextmanager
def serve_half_batch():
    """The dispatcher scores the first half of each batch and drops the
    rest."""
    from repro.query.server import QueryServer
    dispatch = QueryServer._dispatch

    def half(self, batch, fold, version):
        return dispatch(self, batch[:max(1, len(batch) // 2)], fold, version)

    with _patched(QueryServer, "_dispatch", half):
        yield


@contextlib.contextmanager
def serve_answer_altered():
    """The first document of every fold-in batch is scored one nat low."""
    from repro.query import foldin
    score = foldin.FoldIn.score

    def altered(self, *a, **kw):
        res = score(self, *a, **kw)
        res.doc_ll = res.doc_ll.copy()
        res.doc_ll[0] -= 1.0
        return res

    with _patched(foldin.FoldIn, "score", altered):
        yield


TRAIN = {"state_unchanged": train_state_unchanged,
         "half_batch": train_half_batch,
         "token_altered": train_token_altered}
SERVE = {"state_unchanged": serve_state_unchanged,
         "half_batch": serve_half_batch,
         "answer_altered": serve_answer_altered}
