"""Render an obs dump (``repro-obs/1`` JSON) as a human-readable report.

Accepts either shape (``docs/OBSERVABILITY.md``):

* a single registry dump — the dict ``Registry.dump()`` returns (what
  ``examples/reachability.py`` prints, or a file you wrote yourself);
* a benchmark bundle — ``BENCH_obs.json`` from
  ``benchmarks/graph_reachability.py``, with per-graph dumps under
  ``"graphs"``.

For each registry it prints the counters, gauges, histograms (with an
ASCII bar per value — they are exact integer histograms, so every value
is a row), span timings, and the bounded event log, plus the derived
``fastpath_frac`` summary when the FPSP counters are present.

Usage:
    python tools/obs_report.py BENCH_obs.json
    python tools/obs_report.py dump.json --section histograms
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BAR_WIDTH = 40
SECTIONS = ("counters", "gauges", "histograms", "spans", "events")


def _bar(count: int, peak: int) -> str:
    n = max(1, round(BAR_WIDTH * count / peak)) if peak else 0
    return "#" * n


def _fastpath_frac(counters) -> float | None:
    # mirror of repro.obs.metrics.fastpath_frac, kept dependency-free so the
    # report runs on a bare artifact download (no PYTHONPATH=src needed)
    ops = counters.get("fastpath.ops", 0)
    if ops:
        return 1.0 - counters.get("fastpath.conflicted", 0) / ops
    eops = counters.get("fastpath.eops", 0)
    if eops:
        return 1.0 - counters.get("fastpath.edge_dup", 0) / eops
    return None


def render_registry(dump: dict, *, section: str | None = None,
                    out=sys.stdout) -> None:
    if not dump.get("enabled", True):
        print("  (registry disabled — no data)", file=out)
        return

    def want(name: str) -> bool:
        return section is None or section == name

    counters = dump.get("counters", {})
    if want("counters") and counters:
        print("  counters:", file=out)
        width = max(len(k) for k in counters)
        for k, v in counters.items():
            print(f"    {k:<{width}}  {v}", file=out)
        ff = _fastpath_frac(counters)
        if ff is not None:
            print(f"    {'-> fastpath_frac':<{width}}  {ff:.4f}", file=out)

    gauges = dump.get("gauges", {})
    if want("gauges") and gauges:
        print("  gauges:", file=out)
        for k, v in gauges.items():
            print(f"    {k}  {v:.4g}", file=out)

    hists = dump.get("histograms", {})
    if want("histograms") and hists:
        print("  histograms:", file=out)
        for name, h in hists.items():
            print(f"    {name}  (n={h['count']} mean={h['mean']:.2f} "
                  f"p50={h['p50']} p99={h['p99']} max={h['max']})", file=out)
            counts = {int(k): v for k, v in h.get("counts", {}).items()}
            peak = max(counts.values(), default=0)
            for val in sorted(counts):
                print(f"      {val:>6}  {counts[val]:>8}  "
                      f"{_bar(counts[val], peak)}", file=out)

    spans = dump.get("spans", {})
    if want("spans") and spans:
        print("  spans:", file=out)
        for name, s in spans.items():
            print(f"    {name}  n={s['count']} total={s['total_ms']:.2f}ms "
                  f"mean={s['mean_ms']:.3f}ms p50={s['p50_ms']:.3f}ms "
                  f"p99={s['p99_ms']:.3f}ms max={s['max_ms']:.3f}ms",
                  file=out)

    events = dump.get("events", [])
    if want("events") and events:
        print(f"  events ({len(events)}"
              + (f", {dump['dropped_events']} dropped" if
                 dump.get("dropped_events") else "") + "):", file=out)
        for ev in events:
            fields = " ".join(f"{k}={v}" for k, v in ev.items() if k != "event")
            print(f"    {ev.get('event', '?')}  {fields}", file=out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("path", type=Path, help="registry dump or BENCH_obs.json")
    ap.add_argument("--section", choices=SECTIONS, default=None,
                    help="print only one section")
    args = ap.parse_args(argv)

    try:
        data = json.loads(args.path.read_text())
    except (OSError, ValueError) as e:
        print(f"::error::unreadable obs dump ({args.path}: {e})")
        return 1

    if "graphs" in data:  # benchmark bundle
        meta = {k: v for k, v in data.items() if k != "graphs"}
        print(f"# {meta.get('bench', args.path.name)} "
              f"(backend={meta.get('backend', '?')}, "
              f"quick={meta.get('quick', '?')})")
        for label, dump in data["graphs"].items():
            print(f"\n== {label} ==")
            render_registry(dump, section=args.section)
    elif data.get("schema", "").startswith("repro-obs/"):
        render_registry(data, section=args.section)
    else:
        print(f"::error::{args.path}: neither a repro-obs dump nor a "
              f"BENCH_obs.json bundle")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
