"""Graph API host path: device programs (``XLA Modules`` events) started in
the window per ``bench.apply`` span, the update cell's only request: the
engine pass, the growth check's ``_live_counts``, and the small eager
programs around them, per batch."""

from bench import spans


def read(ctx):
    return spans.programs_per_span(ctx.trace, "bench.apply")
