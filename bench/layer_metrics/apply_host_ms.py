"""Graph API host path: each ``bench.apply`` span's wall time less the device
busy time inside it, per batch (ms): padding, batch building, the growth
check and the blocking reads, with the device idle."""


def read(ctx):
    wall = ctx.trace.durations("bench.apply")
    busy = ctx.trace.busy_within("bench.apply")
    return (sum(wall) - sum(busy)) / len(wall) * 1e-6 if wall else None
