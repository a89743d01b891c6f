"""Operations and bytes that a kernel's work needs, from its shapes alone,
and the share of the chip's roofline that a measured time reaches.  The
counts describe the algorithm, not an implementation: padding, layout and
recomputation of a particular kernel are not counted."""
from __future__ import annotations

import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")

# Lloyd steps of every k-means run of phase 1's grouping: a fixed count of
# the algorithm as the grouping runs it (paper Sec. IV-B), not a knob
LLOYD_STEPS = 32


def peaks(device_kind: str) -> dict:
    """The chip's peaks; a device missing from ``peaks.json`` is an error."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS}")
    return table[device_kind]


def lloyd_step_flops(n: int, k: int, f: int) -> int:
    """One Lloyd step over n points of f features and k centers: the
    squared distance of every point to every center (f subtractions, f
    multiplications, f - 1 additions), the least of k, and the point's
    share of its center's sum and count."""
    return n * (k * (3 * f - 1) + (k - 1) + f + 1)


def lloyd_step_bytes(n: int, k: int, f: int, itemsize: int = 4) -> int:
    """Points and centers read once; label, squared distance, sums and
    counts written once."""
    return itemsize * (n * f + k * f + 2 * n + k * f + k)


def share(flops: float, nbytes: float, seconds: float, pk: dict) -> tuple:
    """(percent of the roofline reached, the bound: "compute" or
    "memory") for work done in ``seconds``."""
    t_flops = flops / pk["flops_bf16"]
    t_bytes = nbytes / pk["hbm_bytes_per_s"]
    bound = max(t_flops, t_bytes)
    return 100.0 * bound / seconds, "compute" if t_flops >= t_bytes else "memory"
