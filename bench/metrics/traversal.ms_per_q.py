"""Device time of the traversal and fusion per request answered, in
milliseconds: the union of the executions of the XLA modules they run
as. Read off a chip trace of this cell: the h-hop push runs eagerly, as
``jit_scan`` (the hops), ``jit_scatter-add`` (out-degrees and seed mass)
and ``jit__mean`` (the mean over hops); fusion is ``jit__fuse_candidates``.

Every module named must have run in the window, or the reader returns
nothing (and the cell, which lists this metric, reports none): a program
change that renames one must bring the new name here, in a tracing PR,
rather than read as a gain. Changes that rename them: putting
``core/traversal.py``'s hop loop, or ``run_traverse`` around it, under
``jax.jit`` (the three eager modules then run as one module named after
the jitted function); renaming ``core/index.py:_fuse_candidates``.
"""
UNIT = "ms"
SELECT = {"modules": ["jit_scan", "jit_scatter-add", "jit__mean",
                      "jit__fuse_candidates"]}


def read(run):
    parts = run.trace.selected_parts.get("traversal.ms_per_q", {})
    if run.answered <= 0 or not parts or min(parts.values()) <= 0:
        return None
    return 1e3 * run.trace.selected_s["traversal.ms_per_q"] / run.answered
