"""Share of the traced window in which no operation ran on the device,
in percent, in the closed-loop batch cell: one minus the union of device
operation intervals over the window (``trace_reduce``)."""
UNIT = "%"


def read(run):
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return 100.0 * run.trace.idle_share
