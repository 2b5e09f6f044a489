"""repro.obs — wait-free telemetry: metrics, spans, and probe health.

The paper's performance argument is statistical — the FPSP slow path is
*rare* (§3.4), helping rounds are *bounded*, the hash table stays *healthy*
— and this package is how the repro measures those claims at runtime
instead of inferring them from wall clock.  Two halves:

* :mod:`repro.obs.metrics` — a thread-safe registry of counters, gauges,
  integer histograms, context-manager spans (``jax.profiler`` annotations,
  timed on the wall clock when enabled) and bounded structured events,
  plus the no-op twin every code path holds when observability is off.
  Enable via ``WaitFreeGraph(obs=...)`` or the ``REPRO_OBS`` environment
  variable.
* :mod:`repro.obs.probes` — post-hoc probe-chain health derivations over
  the hash tables (physical per-table histograms, the shard-count-invariant
  canonical-directory histogram).

**Overhead contract** (the bit-identity discipline): every metric is
derived from arrays the jitted programs already compute — stats vectors,
conflict masks, claim-round counters, BFS level maps — via small
post-device host reductions.  Enabling observability never changes a jitted
program, so obs-on and obs-off runs produce byte-identical graph states and
query answers (pinned by ``tests/test_obs.py``).  When disabled, every
recording call is a method on the shared no-op registry: no locks, no
dict writes, no device syncs; a span costs one profiler annotation, which
is dropped unless a profiler session is active.

Metric catalog, span naming convention, and the ``dump()`` JSON schema:
``docs/OBSERVABILITY.md``.
"""

from .metrics import (
    DEVICE_SCOPES,
    NOOP,
    SPAN_PREFIXES,
    NoopRegistry,
    Registry,
    active,
    counter,
    event,
    fastpath_frac,
    from_env,
    gauge,
    hist,
    resolve,
    span,
    use,
)

__all__ = [
    "Registry",
    "NoopRegistry",
    "NOOP",
    "active",
    "use",
    "resolve",
    "from_env",
    "counter",
    "gauge",
    "hist",
    "event",
    "span",
    "fastpath_frac",
    "SPAN_PREFIXES",
    "DEVICE_SCOPES",
]
