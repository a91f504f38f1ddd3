"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

"TPU v5 lite" (TPU v5e): Google Cloud documentation, "TPU v5e" -- 197
TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s chip-to-chip interconnect (four
ICI links of 50 GB/s each).  Copied from ``src/repro/launch/roofline.py``
so that no later change to the program moves the yardstick.  A kind that
is not in the table is an error, never a default.
"""

PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "link_bw": 50e9},
}


def peaks(device_kind: str) -> dict:
    """``flops`` (FLOP/s), ``hbm_bw`` and ``link_bw`` (bytes/s) of one
    chip of ``device_kind``; ``KeyError`` for a kind with no entry."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def least_time(flops: float, nbytes: float, device_kind: str):
    """The least time the chip could take for ``flops`` operations moving
    ``nbytes`` bytes, and which of the two bounds it: ``(seconds,
    "compute" | "memory")``."""
    p = peaks(device_kind)
    t_c, t_m = flops / p["flops"], nbytes / p["hbm_bw"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
