"""The program's own spans and device scopes in a trace.

``bench/trace_reduce.py`` keeps the benchmark's ``bench.*`` spans.  The
program opens spans of its own (``repro.obs``: ``graph.apply`` and its
children ``graph.apply.prepare``, ``.dispatch``, ``.wait``,
``.growth_check``, ``.readback``; ``graph.bfs_batch`` and its children;
``csr.*``, ``maintenance.*``), all ``jax.profiler`` annotations on the same
clock as the device's ops, and its jitted programs name their ops by
``jax.named_scope`` (``engine.vertex_wave``, ``engine.stab_wave``,
``engine.edge_wave``, ``traversal.frontier_expand``,
``traversal.level_update``).  This module reads both:

* :func:`reduce` / :func:`read` give a :class:`SpanTrace`: the reduced
  trace of ``trace_reduce`` (every reading of it unchanged) with the
  program's spans beside the benchmark's, its idle time split by the
  innermost open span, and each span's children;
* :func:`op_scopes` maps each device op of a trace to its scope.  The trace
  names an op by its HLO instruction; the scope is in that instruction's
  ``op_name`` metadata, which the trace does not carry.  So the programs the
  cells' request kinds list (``programs`` in ``bench/steps/<kind>.py``) whose
  names the trace's ``XLA Modules`` show are compiled again at their cells'
  shapes on the attached device, and each instruction is mapped by name.  A
  compiled program is used only if it holds every op name the trace shows
  for that module.

A program without the spans or the scopes (an older checkout) yields none:
the readings that need them are then absent, not wrong.
"""

from __future__ import annotations

import glob
import importlib
import json
import os
import re
import sys
import weakref
from collections import defaultdict
from types import SimpleNamespace

import numpy as np

from bench import trace_reduce

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _exported(name: str) -> tuple:
    import repro.obs

    return tuple(getattr(repro.obs, name, ()))


class SpanTrace(trace_reduce.Trace):
    """A reduced trace whose ``spans`` hold the program's spans too.

    The benchmark's spans follow one another; the program's nest inside
    them and inside each other.  Every reading of ``trace_reduce.Trace``
    gives the same value here for a ``bench.`` span; ``idle_by_span``
    splits idle time by the innermost open span instead."""

    def idle_by_span(self, k: int = 10) -> list[list]:
        """[[span, seconds]]: the device's idle time in the window
        (averaged over the devices), split by the innermost span the host
        had open (``WINDOW_SPAN`` where none was), largest first.  Idle
        time under a ``bench.`` span that no program span covers keeps the
        ``bench.`` span's name; the parts sum to the window's idle time."""
        total: dict[str, float] = defaultdict(float)
        idle = list(self._idle())
        for name, a, b in self.segments():
            for idle_s, idle_e in idle:
                total[name] += trace_reduce.covered(idle_s, idle_e, a, b) / len(idle)
        top = sorted(total.items(), key=lambda kv: -kv[1])[:k]
        return [[n, x * 1e-9] for n, x in top]

    def segments(self) -> list[tuple]:
        """The window cut at every span's start and end, each piece with
        the innermost span open over it: [(name, start, end)]."""
        a, b = self.window
        events = []
        for name, ivs in self.spans.items():
            if name == trace_reduce.WINDOW_SPAN:
                continue
            for s, e in ivs:
                s, e = max(s, a), min(e, b)
                if e > s:
                    # at one instant, ends before starts; a longer span
                    # opens before a shorter one that starts with it
                    events += [(s, 1, -e, name), (e, 0, 0.0, name)]
        events.sort()
        out, stack, t = [], [trace_reduce.WINDOW_SPAN], a
        for x, opening, _, name in events:
            if x > t:
                out.append((stack[-1], t, x))
                t = x
            if opening:
                stack.append(name)
            else:
                # the innermost open span of that name closes
                i = len(stack) - 1 - stack[::-1].index(name)
                del stack[i]
        if b > t:
            out.append((stack[-1], t, b))
        return out

    def _idle(self):
        a, b = self.window
        for us, ue in self.busy.values():
            idle_s = np.concatenate([[a], ue])
            idle_e = np.concatenate([us, [b]])
            keep = idle_e > idle_s
            yield idle_s[keep], idle_e[keep]

    def idle_within(self, name: str) -> list[float]:
        """Per span of ``name``, in order: device idle ns inside it."""
        return [d - x for d, x in zip(self.durations(name), self.busy_within(name))]

    def children(self, name: str, start: float, end: float) -> list[tuple]:
        """The spans named ``<name>.<part>`` inside [start, end], by start:
        [(name, start, end)]."""
        depth = name.count(".") + 1
        out = [
            (n, s, e)
            for n, ivs in self.spans.items()
            if n.startswith(name + ".") and n.count(".") == depth
            for s, e in ivs
            if start <= s and e <= end
        ]
        return sorted(out, key=lambda x: x[1])

    def longest(self, name: str, k: int = 5) -> list[dict]:
        """The ``k`` longest spans of ``name``, each split by its child
        spans: wall and device busy time in ms, and when it started
        relative to the window."""
        ivs = self.spans.get(name, [])
        order = sorted(range(len(ivs)), key=lambda i: ivs[i][0] - ivs[i][1])[:k]
        out = []
        for i in order:
            s, e = ivs[i]
            parts = []
            for n, cs, ce in self.children(name, s, e):
                parts.append([n, (ce - cs) * 1e-6, self._busy(cs, ce) * 1e-6])
            covered = sum(p[1] for p in parts)
            out.append(
                {
                    "span": name,
                    "index": i,
                    "at_s": (s - self.window[0]) * 1e-9,
                    "wall_ms": (e - s) * 1e-6,
                    "busy_ms": self._busy(s, e) * 1e-6,
                    "children": parts,
                    "outside_children_ms": (e - s) * 1e-6 - covered,
                }
            )
        return out

    def shifted(self, ns: float) -> "SpanTrace":
        """This trace with every device timestamp moved by ``ns``."""

        def move(planes):
            return {dev: (names, s + ns, e + ns) for dev, (names, s, e) in planes.items()}

        return SpanTrace(move(self.ops), self.spans, move(self.modules))

    def _busy(self, a: float, b: float) -> float:
        return float(np.mean([trace_reduce.covered(s, e, a, b) for s, e in self.busy.values()]))


def reduce(pd) -> SpanTrace:
    """The reduction of ``trace_reduce.reduce`` with the program's spans
    (those whose names start with ``repro.obs.SPAN_PREFIXES``) kept too."""
    base = trace_reduce.reduce(pd)
    prefixes = _exported("SPAN_PREFIXES")
    spans = {n: list(v) for n, v in base.spans.items()}
    if prefixes:
        for plane in pd.planes:
            if plane.name.startswith(trace_reduce.DEVICE_PLANE_PREFIX):
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(prefixes):
                        spans.setdefault(ev.name, []).append((ev.start_ns, ev.end_ns))
    for v in spans.values():
        v.sort()
    return SpanTrace(base.ops, spans, base.modules)


def read(trace_dir: str) -> SpanTrace:
    """Reduce the one ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, found {len(paths)}")
    return reduce(ProfileData.from_file(paths[0]))


# -- programs per request ------------------------------------------------------


def programs_per_span(trace: trace_reduce.Trace, name: str) -> float | None:
    """Device programs (``XLA Modules`` events) started in the window, per
    span of ``name``, averaged over the devices.  Counted over the whole
    window, not span by span: the trace's device timestamps run early
    against the host's by a millisecond or two, which would move a program
    dispatched near a span's start across its edge.  So it is a count per
    request only in a cell whose requests are all ``name`` spans, and whose
    own work between requests runs nothing on the device."""
    n = len(trace.spans.get(name, []))
    if not n or not trace.modules:
        return None
    a, b = trace.window
    started = [np.count_nonzero((s >= a) & (s <= b)) for _, s, _ in trace.modules.values()]
    return float(np.mean(started)) / n


# -- the device's clock against the host's --------------------------------------

# the program span that dispatches each program, and the one that reads its result
DISPATCHED_IN = {
    "jit_apply_batch": "graph.apply.dispatch",
    "jit__live_counts": "graph.apply.growth_check",
    "jit_bfs_levels": "graph.bfs_batch.dispatch",
}
READ_IN = {
    "jit_apply_batch": "graph.apply.wait",
    "jit__live_counts": "graph.apply.growth_check",
    "jit_bfs_levels": "graph.bfs_batch.readback",
}


def _nearest(points: np.ndarray, x: np.ndarray) -> np.ndarray:
    """For each of ``x``, the nearest of the sorted ``points``."""
    i = np.searchsorted(points, x)
    left = points[np.clip(i - 1, 0, len(points) - 1)]
    right = points[np.clip(i, 0, len(points) - 1)]
    return np.where(np.abs(x - left) <= np.abs(right - x), left, right)


def clock_offset(trace: trace_reduce.Trace) -> tuple[float, float] | None:
    """Bounds, in ns, on the shift that puts the device's timestamps on
    the host's clock: no program starts before the span that dispatches it
    opens (the low bound), nor ends after the span that reads its result
    closes (the high bound).  Each program of ``DISPATCHED_IN`` that starts
    in the window is paired with the span of its kind whose start, and the
    one whose end, lies nearest.  None where the trace shows no such
    program, as in a trace without the program's spans."""
    a, b = trace.window
    lo, hi = [], []
    for names, starts, ends in trace.modules.values():
        short = np.asarray([_module_short(n) for n in names])
        inside = (starts >= a) & (starts <= b)
        for module, span in DISPATCHED_IN.items():
            ivs = np.asarray(trace.spans.get(span, []), float).reshape(-1, 2)
            read = np.asarray(trace.spans.get(READ_IN[module], []), float).reshape(-1, 2)
            sel = inside & (short == module)
            if not sel.any() or not len(ivs) or not len(read):
                continue
            lo.append(np.max(_nearest(ivs[:, 0], starts[sel]) - starts[sel]))
            hi.append(np.min(_nearest(np.sort(read[:, 1]), ends[sel]) - ends[sel]))
    if not lo:
        return None
    return float(max(lo)), float(min(hi))


# -- device scopes -------------------------------------------------------------

_HLO_LINE = re.compile(r'^\s*(?:ROOT\s+)?(%[^\s=]+) = .*?metadata=\{[^}]*?op_name="([^"]*)"')
_MAPS: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
_KEYS: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def scope_of(op_name: str, scopes: tuple) -> str | None:
    """The innermost of ``scopes`` on an op's name stack, or None."""
    found = None
    for part in op_name.split("/"):
        if part in scopes:
            found = part
    return found


def hlo_scopes(hlo_text: str, scopes: tuple) -> dict[str, str | None]:
    """{instruction name: its innermost scope or None} of a compiled
    module's text."""
    out: dict[str, str | None] = {}
    for line in hlo_text.splitlines():
        m = _HLO_LINE.match(line)
        if m:
            out[m.group(1)] = scope_of(m.group(2), scopes)
        else:
            m = re.match(r"^\s*(?:ROOT\s+)?(%[^\s=]+) = ", line)
            if m:
                out.setdefault(m.group(1), None)
    return out


def compiled_text(fn, args) -> str:
    """The optimized HLO text of the jitted ``fn`` at ``args``, compiled
    afresh: with JAX's in-memory caches cleared and the persistent compile
    cache off.  The persistent cache's key leaves the ops' metadata out, so
    an executable it holds, or one loaded from it earlier in the process,
    may have been compiled from a program whose scopes differ, and its text
    carries those."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()
    try:
        return fn.lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _op_short(name: str) -> str:
    return name.split(" = ")[0]


def _module_short(name: str) -> str:
    return name.split("(")[0]


def _shapes(cv: int, ce: int) -> SimpleNamespace:
    """Abstract arguments at a configuration's capacities, from the
    program's own constructors."""
    import jax
    import jax.numpy as jnp

    from repro.core import traversal
    from repro.core.types import OpBatch, make_state

    def state():
        return jax.eval_shape(lambda: make_state(cv, ce))

    def vector(n):
        return jax.ShapeDtypeStruct((n,), jnp.int32)

    return SimpleNamespace(
        state=state,
        batch=lambda n: OpBatch(vector(n), vector(n), vector(n), vector(n)),
        csr=lambda: jax.eval_shape(traversal.build_csr, state()),
        vector=vector,
    )


def _candidates(wanted: set) -> list:
    """(module name, jitted function, abstract arguments) of every program
    a cell of ``BENCHMARK.json`` lists whose module name is in ``wanted``."""
    from bench import harness

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    out, seen = [], set()
    for w in spec["workloads"]:
        _, cfg, traffic = harness.cell_files(spec, w["name"])
        shapes = _shapes(cfg["v_capacity"], cfg["e_capacity"])
        for step in traffic["steps"]:
            kind = importlib.import_module(f"bench.steps.{step['kind']}")
            for label, fn, args in kind.Step.programs(step, shapes):
                module = f"jit_{getattr(fn, '__name__', '')}"
                key = (module, label, cfg["v_capacity"], cfg["e_capacity"])
                if module in wanted and hasattr(fn, "lower") and key not in seen:
                    seen.add(key)
                    out.append((module, fn, args))
    return out


def _op_keys(trace: trace_reduce.Trace) -> dict:
    """{device: (module name, op name) per op event}, computed once."""
    if trace not in _KEYS:
        keys = {}
        for dev, (names, starts, _) in trace.ops.items():
            m_names, m_s, _ = trace.modules.get(dev, ([], np.zeros(0), None))
            owner = np.searchsorted(m_s, starts, side="right") - 1
            modules = [_module_short(n) for n in m_names] + ["?"]
            keys[dev] = [(modules[o], _op_short(n)) for n, o in zip(names, owner.tolist())]
        _KEYS[trace] = keys
    return _KEYS[trace]


def program_scopes(trace: trace_reduce.Trace, scopes: tuple | None = None) -> dict:
    """{(module name, op name): scope} of the programs a cell lists whose
    module the trace shows, from their compiled text at the cells' shapes
    on the attached device; a compiled program that lacks an op name the
    trace shows for its module is not the one that ran, and is left out."""
    if scopes is None:
        scopes = _exported("DEVICE_SCOPES")
    if not scopes:
        return {}
    seen: dict[str, set] = defaultdict(set)
    for ks in _op_keys(trace).values():
        for module, op in set(ks):
            seen[module].add(op)
    mapping, tried, matched = {}, defaultdict(int), defaultdict(int)
    for module, fn, args in _candidates(set(seen)):
        found = hlo_scopes(compiled_text(fn, args), scopes)
        tried[module] += 1
        if seen[module] <= set(found):
            matched[module] += 1
            mapping.update({(module, op): sc for op, sc in found.items() if sc is not None})
    for module in sorted(tried):
        if not matched[module]:
            _log(
                f"scopes: none of the {tried[module]} programs compiled for {module} holds "
                "every op the trace shows for it; its ops get no scope"
            )
        elif matched[module] > 1:
            _log(
                f"scopes: {matched[module]} programs compiled for {module} hold every op "
                "the trace shows for it; the last one's scopes are kept"
            )
    return mapping


def op_scopes(trace: trace_reduce.Trace, mapping: dict | None = None) -> dict:
    """{device: the scope of each op event, or None}: by ``mapping``
    ({(module name, op name): scope}), else by :func:`program_scopes`
    (computed once per trace); empty when no op of the trace has a scope."""
    if mapping is None:
        if trace not in _MAPS:
            _MAPS[trace] = op_scopes(trace, program_scopes(trace))
        return _MAPS[trace]
    out = {
        dev: np.asarray([mapping.get(k) for k in ks], object)
        for dev, ks in _op_keys(trace).items()
    }
    if not any(any(x is not None for x in lab) for lab in out.values()):
        return {}
    return out


def scoped_busy(trace: trace_reduce.Trace, labels: dict, scope: str, span: str) -> list[float]:
    """Per span of ``span``, in order: device ns inside it during which an
    op under ``scope`` ran (the union of those ops' intervals), averaged
    over the devices; ``labels`` is :func:`op_scopes`.  Empty when no op is
    under ``scope``."""
    if not any(np.any(lab == scope) for lab in labels.values()):
        return []
    a, b = trace.window
    per_dev = []
    for dev, (_, starts, ends) in trace.ops.items():
        sel = labels[dev] == scope
        us, ue = trace_reduce.union(np.clip(starts[sel], a, b), np.clip(ends[sel], a, b))
        per_dev.append([trace_reduce.covered(us, ue, s, e) for s, e in trace.spans.get(span, [])])
    return np.mean(np.asarray(per_dev, float), axis=0).tolist()


def scoped_runs(trace: trace_reduce.Trace, labels: dict, scope: str, span: str) -> int | None:
    """How many times the ops under ``scope`` ran inside the spans of
    ``span``, on the first device: the most runs of any one op under it (an
    op in a loop body runs once per iteration), summed over the spans."""
    if not labels or not trace.spans.get(span):
        return None
    dev = sorted(trace.ops)[0]
    _, starts, _ = trace.ops[dev]
    ivs = trace.spans[span]
    a = np.asarray([s for s, _ in ivs], float)
    b = np.asarray([e for _, e in ivs], float)
    i = np.searchsorted(a, starts, side="right") - 1
    inside = (i >= 0) & (starts <= b[np.clip(i, 0, None)]) & (labels[dev] == scope)
    if not inside.any():
        return None
    keys = _op_keys(trace)[dev]
    runs: dict[tuple, int] = defaultdict(int)
    for j in np.flatnonzero(inside).tolist():
        runs[keys[j]] += 1
    return max(runs.values())
