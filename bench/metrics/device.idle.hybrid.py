"""Share of the device's time with a call in flight in which no
operation ran, in percent, in the open-loop hybrid cell: the device's
idle time inside the union of the harness's ``bench.search`` call
annotations, over busy time plus that idle time (``trace_reduce``). The
open loop's quiet spells, with no request in flight, are left out: a
faster traversal does not raise this share by leaving the device idle
longer between arrivals."""
UNIT = "%"


def read(run):
    if run.trace is None:
        return None
    share = run.trace.idle_share_inflight
    return None if share is None else 100.0 * share
