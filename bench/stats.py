"""Small statistics shared by the metric readers."""
from __future__ import annotations

import math
from typing import Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank ``q``-th percentile (0 < q <= 100); a missing value
    (inf, NaN) ranks above every served one. None for no values."""
    if not values:
        return None
    xs = sorted(math.inf if v != v else v for v in values)
    return xs[max(math.ceil(q / 100.0 * len(xs)) - 1, 0)]
