"""Seconds of the program's ``index.communities`` span: the host Louvain
sweep inside ``ingest``."""
UNIT = "s"


def read(run):
    return run.communities_s
