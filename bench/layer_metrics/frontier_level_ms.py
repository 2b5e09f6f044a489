"""Kernels: device time of the ops under the ``traversal.frontier_expand``
scope inside the ``bench.query`` spans, over the frontier expansions the
trace shows there (the runs of the level loop's body, counted from the
trace), per expansion (ms)."""

from bench import spans


def read(ctx):
    labels = spans.op_scopes(ctx.trace)
    busy = spans.scoped_busy(ctx.trace, labels, "traversal.frontier_expand", "bench.query")
    runs = spans.scoped_runs(ctx.trace, labels, "traversal.level_update", "bench.query")
    return sum(busy) / runs * 1e-6 if busy and runs else None
