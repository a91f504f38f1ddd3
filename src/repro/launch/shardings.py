"""Sharding rules: param/optimizer/batch/cache PartitionSpecs per mesh.

Doctrine (the InferSpark partitioning carried to the LM side): shard the big
axes, replicate the small ones, and only shard a dim when it divides the mesh
axis — otherwise fall back to replication for that dim (recorded in the
dry-run JSON so the roofline shows the cost).

- TP ("model" axis): vocab/logits, attention heads (or head_dim when the head
  count doesn't divide 16 — e.g. gemma3's 8 Q heads), d_ff, MoE experts (EP),
  RG-LRU/SSD inner width.
- DP ("pod","data"): batch; the sequence axis instead when batch=1
  (long_500k context parallelism).
- FSDP (optional, "data" only so param all-gathers stay intra-pod): the
  non-TP dim of every matrix, ZeRO-style; optimizer states follow params.
"""

from __future__ import annotations

import re

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, RunConfig
from .mesh import axis_size, data_axes, model_axis


def _div(n: int, mesh, axes) -> bool:
    return axes is not None and n % axis_size(mesh, axes) == 0


class Rules:
    def __init__(self, cfg: ArchConfig, run: RunConfig, mesh):
        self.cfg, self.run, self.mesh = cfg, run, mesh
        self.dp = data_axes(mesh)
        self.tp = model_axis(mesh)
        self.fsdp = "data" if (run.fsdp and "data" in mesh.axis_names) else None

    # -- helpers ----------------------------------------------------------
    def _mt(self, dim: int):
        """'model' if it divides, else None."""
        return self.tp if _div(dim, self.mesh, self.tp) else None

    def _fs(self, dim: int):
        return self.fsdp if _div(dim, self.mesh, self.fsdp) else None

    def _mat(self, shape, tp_dim: int):
        """Spec for a (possibly layer-stacked) matrix: TP on ``tp_dim`` of the
        trailing 2, FSDP on the other."""
        other = 1 - tp_dim
        spec = [None, None]
        spec[tp_dim] = self._mt(shape[-2 + tp_dim])
        spec[other] = self._fs(shape[-2 + other])
        return P(*([None] * (len(shape) - 2) + spec))

    # -- params -----------------------------------------------------------
    def param_spec(self, path: str, shape) -> P:
        c = self.cfg
        nd = len(shape)
        if re.search(r"embed$", path):
            return P(self._mt(shape[0]), self._fs(shape[1]))
        if re.search(r"lm_head$", path):
            return P(self._fs(shape[0]), self._mt(shape[1]))
        if re.search(r"frontend_proj$", path):
            return P(None, self._mt(shape[1]))
        if re.search(r"(wq|wk|wv)$", path):
            return self._mat(shape, 1)
        if re.search(r"wo$", path) and "ffn" not in path and nd >= 2 \
                and "rglru" not in path:
            return self._mat(shape, 0)
        if re.search(r"router$", path):
            return P(*([None] * (nd - 1) + [self._mt(shape[-1])]))
        if "ffn" in path and nd >= 3 and c.n_experts:       # MoE (E, d, f)
            lead = [None] * (nd - 3)
            e = self._mt(shape[-3])
            if re.search(r"wi$", path):
                return P(*(lead + [e, self._fs(shape[-2]), None]))
            return P(*(lead + [e, None, self._fs(shape[-1])]))
        if "ffn" in path and re.search(r"wi$", path):
            return self._mat(shape, 1)
        if "ffn" in path and re.search(r"wo$", path):
            return self._mat(shape, 0)
        if "rglru" in path or "ssd" in path:
            if re.search(r"(wx|wgate|in_proj)$", path):
                return self._mat(shape, 1)
            if re.search(r"(wo|out_proj)$", path):
                return self._mat(shape, 0)
            if re.search(r"(wr|wi)$", path):
                return self._mat(shape, 1)
            if re.search(r"conv$", path):
                return P(*([None] * (nd - 1) + [self._mt(shape[-1])]))
            if nd >= 1 and re.search(r"lam$", path):
                return P(*([None] * (nd - 1) + [self._mt(shape[-1])]))
        return P(*([None] * nd))                            # norms, scalars

    def params(self, params_shape) -> object:
        flat, treedef = jax.tree_util.tree_flatten_with_path(params_shape)
        specs = []
        for path, leaf in flat:
            pstr = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                            for k in path)
            specs.append(self.param_spec(pstr, leaf.shape))
        return jax.tree_util.tree_unflatten(treedef, specs)

    def opt_state(self, opt_shape, params_spec) -> object:
        """mu/nu follow the params; count is replicated."""
        return {"mu": params_spec, "nu": params_spec, "count": P()}

    # -- batches ----------------------------------------------------------
    def _bs(self, b: int, s: int) -> P:
        """(B, S): batch over DP when divisible, else sequence (SP)."""
        if _div(b, self.mesh, self.dp):
            return P(self.dp, None)
        if _div(s, self.mesh, self.dp):
            return P(None, self.dp)
        return P(None, None)

    def batch(self, batch_shape) -> object:
        out = {}
        for k, v in batch_shape.items():
            if v.ndim >= 2:
                spec = self._bs(v.shape[0], v.shape[1])
                out[k] = P(*(list(spec) + [None] * (v.ndim - 2)))
            else:
                out[k] = P(None)
        return out

    # -- decode cache -----------------------------------------------------
    def cache_leaf(self, path: str, shape) -> P:
        """Cache leaves may carry a leading layer-stack dim (scan repeats)."""
        nd = len(shape)
        name = path.rsplit("/", 1)[-1]
        if name in ("k", "v"):                   # (..., B, S, KV, Dh)
            lead = [None] * (nd - 4)
            b, s, kv, dh = shape[-4:]
            bs = self._bs(b, s)
            if self._mt(kv):                     # enough kv heads: TP on heads
                return P(*(lead + [bs[0], bs[1], self._mt(kv), None]))
            # few kv heads (GQA/MQA): shard the SEQUENCE over model — decode
            # does partial attention per shard + a small softmax-stat psum,
            # instead of re-gathering a head-dim-sharded cache every step
            if self.tp:
                if bs[1] is None and s % axis_size(self.mesh, self.tp) == 0:
                    return P(*(lead + [bs[0], self.tp, None, None]))
                if bs[1] is not None and bs[0] is None:
                    # batch=1 long-context: sequence over data AND model
                    axes = (bs[1] if isinstance(bs[1], tuple)
                            else (bs[1],)) + (self.tp,)
                    if s % axis_size(self.mesh, axes) == 0:
                        return P(*(lead + [None, axes, None, None]))
            return P(*(lead + [bs[0], bs[1], None, self._mt(dh)]))
        if name == "conv":                       # (..., B, W, L)
            return P(*([None] * (nd - 1) + [self._mt(shape[-1])]))
        if name == "h" and nd >= 4:              # ssd state (..., B, H, N, P)
            return P(*([None] * (nd - 3) + [self._mt(shape[-3]), None, None]))
        if name == "h":                          # rglru state (..., B, L)
            return P(*([None] * (nd - 1) + [self._mt(shape[-1])]))
        return P(*([None] * nd))

    def cache(self, cache_shape) -> object:
        flat, treedef = jax.tree_util.tree_flatten_with_path(cache_shape)
        specs = []
        for path, leaf in flat:
            pstr = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                            for k in path)
            specs.append(self.cache_leaf(pstr, leaf.shape))
        return jax.tree_util.tree_unflatten(treedef, specs)


def named(mesh, spec_tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


