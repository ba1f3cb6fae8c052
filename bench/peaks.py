"""Published peaks of one chip, keyed by ``device_kind`` as JAX reports it
(``peaks.json``). A kind that is not in the table is an error."""
from __future__ import annotations

import json
import os

TABLE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peak(kind: str, table: str = TABLE) -> dict:
    with open(table) as fh:
        peaks = json.load(fh)
    if kind not in peaks:
        raise KeyError(f"no published peaks for device kind {kind!r}; "
                       f"known: {sorted(peaks)}")
    return peaks[kind]


def roofline_share(flops: float, bytes_: float, seconds: float,
                   chip: dict) -> float | None:
    """Percent of the roofline: the least time the chip could take (the
    larger of operations over peak FLOP/s and bytes over peak bandwidth)
    over the time taken. None when nothing was timed."""
    if seconds <= 0:
        return None
    least = max(flops / chip["flops_per_s"], bytes_ / chip["bytes_per_s"])
    return 100.0 * least / seconds
