"""The verdict that decides ``correct``: every compared number at or under
its limit. The numbers and their limits come from the configuration's kind
(``kinds/<kind>.py``: ``readings`` against the kind's plain reference, and
``limits``)."""
from __future__ import annotations

from typing import Dict, List, Tuple


def judge(read: Dict[str, float], lim: Dict[str, float]
          ) -> Tuple[bool, List[Dict]]:
    """(correct, [{name, value, limit}]): every reading at or under its
    limit (a NaN reading fails)."""
    rows = [{"name": k, "value": read[k], "limit": lim[k]} for k in lim]
    return all(r["value"] <= r["limit"] for r in rows), rows
