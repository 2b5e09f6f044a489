"""Attribute collective traffic to source ops: the §Perf profiler.

  PYTHONPATH=src:. python -m benchmarks.collective_sites results/hlo/<tag>.hlo.gz

For each collective op: exec-weighted bytes (trip-count multiplied through
the loop nest *summing over every call site* — remat clones share
computations, so max-propagation undercounts), replica-group size, and the
jax op_name metadata (maps to a model source line).  Sorted by ring-model
seconds — the top rows are the hillclimb targets.
"""

from __future__ import annotations

import gzip
import sys

from benchmarks.roofline import DRYRUN_DEVICE_KIND, RING_FACTOR, peaks
from repro.launch import hloparse


def site_report(text: str, top: int = 25):
    costs = hloparse.module_costs(text)
    link_bw = peaks(DRYRUN_DEVICE_KIND)["link_bw"]
    rows = []
    for kind, b, g, m, name in costs.collective_sites:
        ring = RING_FACTOR.get(kind, lambda g: 1.0)(max(int(g), 1))
        rows.append({
            "kind": kind, "bytes": b, "mult": m, "group": g,
            "seconds": b * m * ring / link_bw,
            "op_name": name,
        })
    rows.sort(key=lambda r: -r["seconds"])
    return rows[:top] if top else rows


def main():
    path = sys.argv[1]
    text = gzip.open(path, "rt").read() if path.endswith(".gz") else open(path).read()
    rows = site_report(text, top=0)
    total = sum(r["seconds"] for r in rows)
    print(f"{len(rows)} collective sites, {total:.3f}s ring-model total; top 25:")
    for r in rows[:25]:
        print(f"  {r['seconds']:8.3f}s  {r['kind']:<18} {r['bytes']/1e6:9.1f}MB "
              f"x{r['mult']:<7.0f} g={r['group']:<4} {r['op_name'][-95:]}")


if __name__ == "__main__":
    main()
