"""Device-to-host reads per micro-batch call in the window: the
program's ``executor.syncs`` counter (one per read through
``repro.obs.to_host``) over the count of its ``serving.batch_q``
histogram (one per bucketed call). Both start at the ``obs.reset()``
that opens the window, and nothing the harness runs after the window
reads through ``to_host``. Reads nothing where the program keeps no such
counter: renaming ``executor.syncs`` (``repro/obs/sync.py``) or
``serving.batch_q`` (``repro/serving/retrieval.py``) silences it."""
UNIT = "syncs/call"


def read(run):
    from repro import obs
    syncs = obs.registry().counters().get("executor.syncs")
    h = run.batch_q
    if syncs is None or not h or not h.get("count"):
        return None
    return syncs.value / h["count"]
