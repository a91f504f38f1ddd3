"""What several metric readers share."""

import re

# The fused token-plate kernel (``kernels/fused_zstats.py``) in a TPU
# trace.  Op names there are the HLO instruction's text and carry no
# kernel name, so the kernel is known by its call's form: a Mosaic custom
# call whose first operand is the scalar-prefetched tile index of each
# token block (``s32[blocks]``) and whose first output is one (8, 128)
# lse tile per block (``f32[8 * blocks, 128]``).
ZSTATS_KERNEL = (r'^%\S+ = \(f32\[\d+,128\]\{[^}]*\}, .*\) custom-call\('
                 r's32\[\d+\]\{[^}]*\} %.*custom_call_target="tpu_custom_call"')


def kernel_seconds(summary: dict, pattern: str) -> float:
    """Device seconds, summed over the chips, of every operation whose
    name matches ``pattern``."""
    return sum(t for dev in summary["devices"]
               for name, t in dev["by_name"].items()
               if re.search(pattern, name)) * 1e-9
