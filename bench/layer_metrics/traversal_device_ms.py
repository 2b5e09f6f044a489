"""Traversal: device busy time inside each ``bench.query`` span, per call (ms)."""


def read(ctx):
    busy = ctx.trace.busy_within("bench.query")
    return sum(busy) / len(busy) * 1e-6 if busy else None
