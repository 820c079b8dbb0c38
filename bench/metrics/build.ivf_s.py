"""Seconds of ``HMGIIndex.ingest`` on the host clock, less the host
community sweep (``build.communities_s``): k-means, the slab, the delta
and the graph store."""
UNIT = "s"


def read(run):
    return run.ingest_s - (run.communities_s or 0.0)
