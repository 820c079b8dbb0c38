"""Runs one cell as ``bench/run.py`` does, and keeps its trace.

    python3 bench/tests/keep_trace.py <out.xplane.pb> --workload <cell> \\
        --seed <n> --seconds <s> --trace 1

The harness reduces its trace and then deletes it; this copies the trace
to ``<out>`` first, so that ``span_reduce`` (or ``trace_reduce``) can read
the same run again by hand. Everything else, the result line included,
is the harness's own.
"""
import pathlib
import shutil
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import run  # noqa: E402


def main(out: str, argv) -> int:
    reduce_trace = run.Harness.reduce_trace

    def keep_then_reduce(self, tracedir):
        files = sorted(pathlib.Path(tracedir).rglob("*.xplane.pb"))
        if files:
            pathlib.Path(out).parent.mkdir(parents=True, exist_ok=True)
            shutil.copy(files[-1], out)
        return reduce_trace(self, tracedir)

    run.Harness.reduce_trace = keep_then_reduce
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], sys.argv[2:]))
