"""Faults for the cells of the ``train_plan`` driver, planted as
``bench/faults.py`` plants its own: each a context manager that patches
the program and undoes the patch on exit.  ``PLAN`` holds these and the
training faults of ``bench/faults.py``."""

from __future__ import annotations

import contextlib

from bench import faults


@contextlib.contextmanager
def sentence_altered():
    """The first token of every minibatch is read as a token of the next
    sentence."""
    from repro.data import store

    sliced = store.slice_sharded

    def altered(template, corpus, groups, caps_fn=None):
        arrays, dirs, caps, n = sliced(template, corpus, groups, caps_fn)
        x = dict(arrays["x"])
        zmap = x["zmap"].copy()
        zmap[0] = (zmap[0] + 1) % caps["z"]
        x["zmap"] = zmap
        return dict(arrays, x=x), dirs, caps, n

    with faults._patched(store, "slice_sharded", altered):
        yield


@contextlib.contextmanager
def stats_psum_left_out():
    """Under a sharding plan each chip keeps its own statistics of the
    global tables: the psum that adds them over the chips is left out."""
    from repro.core import svi
    body = svi._step_body

    def no_psum(program, arrays, state, axis_names=(), local_dirs=(),
                **kw):
        return body(program, arrays, state, axis_names=axis_names,
                    local_dirs=frozenset(program.dirichlets), **kw)

    with faults._patched(svi, "_step_body", no_psum):
        yield


PLAN = {**faults.TRAIN, "sentence_altered": sentence_altered,
        "stats_psum_left_out": stats_psum_left_out}
