"""Work of the seed scan, counted from shapes and fill, and its roofline.

The stable scan of one batch call has to read, at the least, every live
row of every partition that some query of the batch probes, once: d int8
bytes plus its id (4 B) and two float32 affine terms (8 B). It has to do
at the least one multiply-add per dimension for each query and each live
row that query probes. How the implementation gathers, pads or repeats
rows does not enter: a gain has to come from doing this work faster.

The least time the chip needs is the larger of bytes over the HBM
bandwidth and operations over the int8 peak (the highest rate any
arithmetic on the chip reaches), both from ``peaks.json``.
"""
from __future__ import annotations

import json
import pathlib
from typing import Dict, Tuple

import numpy as np

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"
ROW_OVERHEAD_BYTES = 12          # int32 id + float32 vmin + float32 scale


def peaks(device_kind: str) -> Dict[str, float]:
    """The published peaks of ``device_kind``; an unknown device raises."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name} (known: {sorted(table)})")
    return table[device_kind]


def seed_scan_work(probes: np.ndarray, live: np.ndarray,
                   dim: int) -> Tuple[float, float]:
    """(bytes, operations) of one batch call's stable scan.

    probes: (Q, n_probe) partitions each query scans; live: (K,) live rows
    per partition. Bytes: the distinct probed partitions' live rows, once
    each, at ``dim + 12`` bytes. Operations: 2·dim per live row probed,
    summed over queries."""
    probes = np.asarray(probes)
    live = np.asarray(live, np.float64)
    n_bytes = float(np.sum(live[np.unique(probes)])) * (dim
                                                         + ROW_OVERHEAD_BYTES)
    ops = 2.0 * dim * float(np.sum(live[probes]))
    return n_bytes, ops


def least_time(n_bytes: float, ops: float,
               peak: Dict[str, float]) -> Tuple[float, str]:
    """(seconds, which bound) the chip needs at the least."""
    t_mem = n_bytes / peak["hbm_bytes_per_s"]
    t_ops = ops / peak["int8_ops_per_s"]
    return (t_mem, "bytes") if t_mem >= t_ops else (t_ops, "operations")


def roofline_share(least_s: float, device_s: float) -> float:
    """Least time over measured device time, as a share (1 = at the peak)."""
    if device_s <= 0:
        raise ValueError("device time must be positive")
    return least_s / device_s
