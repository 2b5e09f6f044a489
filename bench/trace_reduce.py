"""From a profiler trace to the numbers the per-layer metrics read.

The benchmark records one trace per ``--trace 1`` run, around its measured
window, with its own host spans (``bench.*`` ``TraceAnnotation``\\ s: the
window, each request, and the benchmark's own work between requests).  This
module reads the ``.xplane.pb`` that ``jax.profiler`` wrote and reduces it
to intervals:

* **busy**: per device, the union of the intervals in which an XLA op ran
  (the ``XLA Ops`` line of each ``/device:`` plane), clipped to the window;
* **spans**: the host spans by name, in the order they started;
* from those: busy time inside given spans, device time by op name, and the
  device's idle time by the host span that was open while it was idle.

Everything is in nanoseconds on the trace's own clock; host and device
planes share it.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict

import numpy as np

DEVICE_PLANE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# ops that only hold other ops: their time is their children's
CONTROL_OPS = ("%while", "%conditional", "%cond.", "%call")
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


def union(starts: np.ndarray, ends: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Merge intervals into sorted, disjoint ones."""
    if starts.size == 0:
        return starts, ends
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    reach = np.maximum.accumulate(e)
    # a new interval starts where it begins after everything before it ended
    new = np.ones(s.size, bool)
    new[1:] = s[1:] > reach[:-1]
    idx = np.flatnonzero(new)
    return s[idx], np.maximum.reduceat(e, idx)


def covered(us: np.ndarray, ue: np.ndarray, a: float, b: float) -> float:
    """Length of [a, b] covered by disjoint sorted intervals (us, ue)."""
    i = np.searchsorted(ue, a, side="right")
    j = np.searchsorted(us, b, side="left")
    if j <= i:
        return 0.0
    return float(np.sum(np.minimum(ue[i:j], b) - np.maximum(us[i:j], a)))


class Trace:
    """The reduced trace of one window."""

    def __init__(self, ops: dict, spans: dict, modules: dict | None = None):
        # ops, modules: device plane name -> (names, starts, ends);
        # spans: name -> [(start, end)]
        if not spans.get(WINDOW_SPAN):
            raise ValueError(f"the trace holds no {WINDOW_SPAN} span")
        if not ops:
            raise ValueError("the trace holds no device plane with XLA ops")
        self.window = spans[WINDOW_SPAN][0]
        self.spans = spans
        self.ops = ops
        self.modules = modules or {}
        a, b = self.window
        self.busy = {}
        for dev, (_, s, e) in ops.items():
            us, ue = union(np.clip(s, a, b), np.clip(e, a, b))
            keep = ue > us
            self.busy[dev] = (us[keep], ue[keep])

    @property
    def window_ns(self) -> float:
        return float(self.window[1] - self.window[0])

    def busy_ns(self) -> float:
        """Device busy time in the window, averaged over the devices."""
        return float(np.mean([np.sum(e - s) for s, e in self.busy.values()]))

    def busy_within(self, name: str) -> list[float]:
        """Per span of ``name``, in order: device busy ns inside it,
        averaged over the devices."""
        out = []
        for a, b in self.spans.get(name, []):
            out.append(float(np.mean([covered(s, e, a, b) for s, e in self.busy.values()])))
        return out

    def durations(self, name: str) -> list[float]:
        return [float(b - a) for a, b in self.spans.get(name, [])]

    def top_ops(self, k: int = 10) -> list[list]:
        """[[name, seconds]] of device time in the window, summed over the
        devices: first the programs (``XLA Modules``, by jitted function,
        at most half of ``k``), then the ops that do the work (``XLA Ops``
        less the control ops that only hold others), each named
        ``program/op``; longest first within each."""
        a, b = self.window

        def clipped(s, e):
            return np.clip(e, a, b) - np.clip(s, a, b)

        progs: dict[str, float] = defaultdict(float)
        for names, s, e in self.modules.values():
            for n, x in zip(names, clipped(s, e).tolist()):
                if x > 0:
                    progs[n.split("(")[0]] += x
        out = sorted(progs.items(), key=lambda kv: -kv[1])[: k // 2]
        leaves: dict[str, float] = defaultdict(float)
        for dev, (names, s, e) in self.ops.items():
            m_names, m_s, _ = self.modules.get(dev, ([], np.zeros(0), None))
            owner = np.searchsorted(m_s, s, side="right") - 1
            for n, x, o in zip(names, clipped(s, e).tolist(), owner.tolist()):
                short = n.split(" = ")[0]
                if x > 0 and not short.startswith(CONTROL_OPS):
                    prog = m_names[o].split("(")[0] if o >= 0 else "?"
                    leaves[f"{prog}/{short}"] += x
        out += sorted(leaves.items(), key=lambda kv: -kv[1])[: k - len(out)]
        return [[n, x * 1e-9] for n, x in out]

    def idle_by_span(self, k: int = 10) -> list[list]:
        """[[host span, seconds]]: the device's idle time in the window
        (averaged over the devices), split by the ``bench.`` span the host
        had open (``WINDOW_SPAN`` where none was), largest first.  The
        spans inside the window follow one another; they never overlap."""
        a, b = self.window
        inner = sorted((s, e) for n, ivs in self.spans.items() if n != WINDOW_SPAN for s, e in ivs)
        if any(e0 > s1 for (_, e0), (s1, _) in zip(inner, inner[1:])):
            raise ValueError("bench spans overlap inside the window")
        total: dict[str, float] = defaultdict(float)
        for us, ue in self.busy.values():
            idle_s = np.concatenate([[a], ue])
            idle_e = np.concatenate([us, [b]])
            keep = idle_e > idle_s
            idle_s, idle_e = idle_s[keep], idle_e[keep]
            rest = float(np.sum(idle_e - idle_s))
            for name, ivs in self.spans.items():
                if name == WINDOW_SPAN:
                    continue
                x = sum(covered(idle_s, idle_e, max(s, a), min(e, b)) for s, e in ivs if e > a and s < b)
                total[name] += x / len(self.busy)
                rest -= x
            total[WINDOW_SPAN] += rest / len(self.busy)
        top = sorted(total.items(), key=lambda kv: -kv[1])[:k]
        return [[n, x * 1e-9] for n, x in top]


def read(trace_dir: str) -> Trace:
    """Reduce the one ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, found {len(paths)}")
    return reduce(ProfileData.from_file(paths[0]))


def _events(line):
    names, s, e = [], [], []
    for ev in line.events:
        names.append(ev.name)
        s.append(ev.start_ns)
        e.append(ev.end_ns)
    order = np.argsort(np.asarray(s, float), kind="stable")
    return [names[i] for i in order], np.asarray(s, float)[order], np.asarray(e, float)[order]


def reduce(pd) -> Trace:
    ops, modules = {}, {}
    spans: dict[str, list] = defaultdict(list)
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = _events(line)
                elif line.name == MODULES_LINE:
                    modules[plane.name] = _events(line)
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans[ev.name].append((ev.start_ns, ev.end_ns))
    for v in spans.values():
        v.sort()
    return Trace(ops, dict(spans), modules)
