"""Engines: device busy time inside each ``bench.apply`` span, per batch (ms)."""


def read(ctx):
    busy = ctx.trace.busy_within("bench.apply")
    return sum(busy) / len(busy) * 1e-6 if busy else None
