"""Mean (query, probe) pairs per distinct probed partition over the
window's seed scans: the program's ``seed_scan.queries_per_partition``
histogram (one observation per ``run_seed`` call, from the probes the
workload tracker copies to the host), its sum over its count. It starts
at the ``obs.reset()`` that opens the window. The partition-major scan
reads each probed partition once per call and scores it against all of
these pairs at once, so this is the sharing it finds. Reads nothing where
the program keeps no such histogram: renaming it
(``repro/query/executor.py:run_seed``) silences it."""
UNIT = "q/part"


def read(run):
    from repro import obs
    h = obs.registry().histograms().get("seed_scan.queries_per_partition")
    if h is None or not h.count:
        return None
    return h.total / h.count
