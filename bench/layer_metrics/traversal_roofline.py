"""Traversal programs' share of the HBM roofline (%), over the query calls
the check compared: the least bytes of those calls (counted from the
reference's graph and levels, ``bench/steps/bfs.py``) at the chip's peak
bandwidth, over the device busy time inside their ``bench.query`` spans."""


def read(ctx):
    least = ctx.least_bytes.get("bench.query", {})
    if not least:
        return None
    busy = ctx.trace.busy_within("bench.query")
    t = sum(busy[i] for i in least) * 1e-9
    if t <= 0:
        return None
    return 100.0 * sum(least.values()) / ctx.peaks["hbm_bytes_per_s"] / t
