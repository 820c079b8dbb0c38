"""Mean number of queries per micro-batch call in the window: the sum
over the program's ``serving.batch_q`` histogram over its count."""
UNIT = "q"


def read(run):
    h = run.batch_q
    if not h or not h.get("count"):
        return None
    return h["sum"] / h["count"]
