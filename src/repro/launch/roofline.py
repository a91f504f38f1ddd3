"""Three-term roofline from a compiled (but never executed) step.

    compute term    = HLO_FLOPs / (chips * peak_FLOPs)
    memory term     = HLO_bytes / (chips * HBM_bw)
    collective term = collective_bytes / (chips * link_bw)

FLOPs/bytes come from ``compiled.cost_analysis()``.  Collective bytes are not
in cost_analysis: we parse the post-SPMD HLO text and sum operand sizes of
every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute.  Hardware constants come from :data:`PEAKS`, keyed by the
``device_kind`` jax reports; a kind missing from it is an error, never a
default.
"""

from __future__ import annotations

import re

# Published per-chip peaks, keyed by ``jax.Device.device_kind``.
# "TPU v5 lite" (TPU v5e): Google Cloud documentation, "TPU v5e" — 197
# TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s chip-to-chip interconnect
# (four ICI links of 50 GB/s each).
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "link_bw": 50e9},
}


def peaks(device_kind: str) -> dict:
    """Per-chip ``flops`` (bf16 FLOP/s), ``hbm_bw`` and ``link_bw``
    (bytes/s) of ``device_kind``; raises ``KeyError`` for a kind with no
    published entry in :data:`PEAKS`."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1,
    "f8e5m2": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    """Total bytes of all tensors mentioned in an HLO type string like
    ``f32[8,128]`` or ``(bf16[4,4], bf16[4,4])``."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_stats(hlo_text: str) -> dict:
    """Per-collective-kind output bytes + op counts from HLO text."""
    out = {k: {"bytes": 0, "count": 0} for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        ls = line.strip()
        # HLO op lines look like:  %name = f32[8,128]{1,0} all-reduce(...)
        m = re.match(r"%?[\w.\-]+\s*=\s*([^=]+?)\s+([\w\-]+)\(", ls)
        if not m:
            continue
        shape_str, op = m.group(1), m.group(2)
        kind = None
        for k in _COLLECTIVES:
            if op == k or op.startswith(k + "-"):
                kind = k
                break
        if kind is None:
            continue
        out[kind]["bytes"] += _shape_bytes(shape_str)
        out[kind]["count"] += 1
    out["total_bytes"] = sum(v["bytes"] for k, v in out.items()
                             if isinstance(v, dict))
    out["total_count"] = sum(v["count"] for k, v in out.items()
                             if isinstance(v, dict))
    return out


def roofline(cost: dict, coll: dict, n_chips: int, model_flops: float = 0.0,
             per_device_cost: bool = True, *, device_kind: str) -> dict:
    """The three terms in seconds + bottleneck, at ``device_kind``'s peaks.

    ``cost_analysis`` on an SPMD executable reports per-device numbers
    (the module is the per-device program); set per_device_cost=False if the
    numbers are whole-program.
    """
    pk = peaks(device_kind)
    flops = float(cost.get("flops", 0.0))
    bytes_ = float(cost.get("bytes accessed", 0.0))
    cbytes = float(coll.get("total_bytes", 0))
    div = 1.0 if per_device_cost else float(n_chips)
    t_compute = flops / div / pk["flops"]
    t_memory = bytes_ / div / pk["hbm_bw"]
    t_coll = cbytes / pk["link_bw"]  # HLO collective shapes are per-device
    terms = {"compute_s": t_compute, "memory_s": t_memory,
             "collective_s": t_coll}
    bottleneck = max(terms, key=terms.get)
    out = dict(terms)
    out["bottleneck"] = bottleneck.replace("_s", "")
    out["hlo_flops_per_device"] = flops / div
    out["hlo_bytes_per_device"] = bytes_ / div
    out["collective_bytes_per_device"] = cbytes
    if model_flops:
        total_hlo = flops / div * n_chips
        out["model_flops"] = model_flops
        out["useful_flops_ratio"] = model_flops / max(total_hlo, 1.0)
        # roofline fraction: useful model FLOPs over the time the dominant
        # term implies at peak
        t_dom = max(terms.values())
        out["roofline_fraction"] = (model_flops / n_chips / pk["flops"]) \
            / max(t_dom, 1e-30)
    return out


def train_model_flops(n_params_active: int, n_tokens: int) -> float:
    """MODEL_FLOPS = 6*N*D (dense) / 6*N_active*D (MoE)."""
    return 6.0 * n_params_active * n_tokens


def decode_model_flops(n_params_active: int, batch: int) -> float:
    """One decode step processes ``batch`` tokens at 2*N FLOPs each."""
    return 2.0 * n_params_active * batch
