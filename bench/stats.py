"""Small statistics and roofline arithmetic shared by the metric readers."""
from __future__ import annotations

import math
from typing import Dict, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank ``q``-th percentile (0 < q <= 100); a missing value
    (inf, NaN) ranks above every served one. None for no values."""
    if not values:
        return None
    xs = sorted(math.inf if v != v else v for v in values)
    return xs[max(math.ceil(q / 100.0 * len(xs)) - 1, 0)]


def least_seconds(work: Dict[str, int], items: int, peak: Dict):
    """(least seconds for ``items`` items of ``work`` -- its FLOPs and HBM
    bytes per item -- on a chip of ``peak``, bound) where bound names the
    roofline side that sets it: "compute" or "memory"."""
    t_ops = work["flops"] * items / peak["bf16_flops_per_s"]
    t_bytes = work.get("bytes", 0) * items / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")
