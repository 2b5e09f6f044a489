"""Engines: device time of the ops under the ``engine.stab_wave`` scope
inside each ``bench.apply`` span, per batch (ms)."""

from bench import spans


def read(ctx):
    busy = spans.scoped_busy(ctx.trace, spans.op_scopes(ctx.trace), "engine.stab_wave", "bench.apply")
    return sum(busy) / len(busy) * 1e-6 if busy else None
