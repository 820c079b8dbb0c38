"""Seed scan's share of its roofline, in percent.

Least time of the batch calls' stable-scan work (``work.seed_scan_work``:
the distinct probed partitions' live rows read once, 2·d operations per
live row per query; at the HBM or int8 peak, whichever binds) over the
device time of every operation of the stable scan's compiled step in the
trace: its gather, the Pallas kernel and the top-k merge all sit in the
one XLA module of ``ivf.search``, so moving work between the kernel and
XLA operations changes nothing here.

The module must have run in the window, or the reader returns nothing
(and the cell, which lists this metric, reports none). Changes that
rename it: renaming ``core/ivf.py:search``, or moving the scan out of
that jitted function; a tracing PR brings the new name here.
"""
UNIT = "%"
SELECT = {"modules": ["jit_search"]}


def read(run):
    from bench import work
    parts = run.trace.selected_parts.get("seed_scan_roofline", {})
    device_s = run.trace.selected_s.get("seed_scan_roofline", 0.0)
    if not run.scan_work or not parts or min(parts.values()) <= 0:
        return None
    peak = work.peaks(run.device_kind)
    least = sum(work.least_time(b, o, peak)[0] for b, o in run.scan_work)
    return 100.0 * work.roofline_share(least, device_s)
