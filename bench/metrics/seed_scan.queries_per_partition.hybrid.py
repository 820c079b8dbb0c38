"""``seed_scan.queries_per_partition.batch``'s reading, for the cells that
report ``p95_ms``: single queries find no sharing, so it reads about 1."""
import pathlib
import runpy

_batch = runpy.run_path(str(pathlib.Path(__file__).with_name(
    "seed_scan.queries_per_partition.batch.py")))
UNIT = _batch["UNIT"]
read = _batch["read"]
