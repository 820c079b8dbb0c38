"""Seed-scan work from shapes and fill, and the roofline it gives."""
import pathlib
import sys

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from bench import work  # noqa: E402

V5E = "TPU v5 lite"


def test_hand_worked_batch():
    # two queries over partitions {0, 1} and {1, 2}; partition 3 unprobed
    probes = np.array([[0, 1], [1, 2]])
    live = np.array([10, 20, 30, 5])
    n_bytes, ops = work.seed_scan_work(probes, live, dim=4)
    assert n_bytes == (10 + 20 + 30) * (4 + 12)       # each partition once
    assert ops == 2 * 4 * ((10 + 20) + (20 + 30))      # per query probed


def test_unknown_device_raises():
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("TPU v9 imaginary")


@pytest.mark.parametrize("dim", [384, 4096])
def test_reading_every_live_row_once_at_peak_is_exactly_one(dim):
    peak = work.peaks(V5E)
    probes = np.arange(64).reshape(8, 8)         # every partition once
    live = np.full(64, 16_384)
    n_bytes, ops = work.seed_scan_work(probes, live, dim)
    least, bound = work.least_time(n_bytes, ops, peak)
    at_peak = max(n_bytes / peak["hbm_bytes_per_s"],
                  ops / peak["int8_ops_per_s"])
    assert work.roofline_share(least, at_peak) == 1.0
    assert bound == "bytes"                      # one query per row: memory
    # any slower device time reads below 1, never above
    assert work.roofline_share(least, at_peak * 1.5) < 1.0


def test_operation_bound_when_many_queries_share_rows():
    peak = work.peaks(V5E)
    probes = np.zeros((100_000, 1), int)         # all on one partition
    n_bytes, ops = work.seed_scan_work(probes, np.array([1000]), 384)
    least, bound = work.least_time(n_bytes, ops, peak)
    assert bound == "operations"
    assert least == pytest.approx(ops / peak["int8_ops_per_s"])
