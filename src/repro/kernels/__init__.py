"""Pallas TPU kernels for the perf-critical compute layers.

Each kernel ships three files:
  * ``kernel.py`` — pl.pallas_call + explicit BlockSpec VMEM tiling (TPU
    target; validated with interpret=True on CPU),
  * ``ops.py``    — jit'd public wrapper with backend dispatch,
  * ``ref.py``    — pure-jnp oracle (also the CPU / dry-run path).

Kernels:
  * ``flash_attention`` — training/prefill attention (causal+SWA+GQA).
  * ``paged_attention`` — decode over wfgraph-managed block tables.
  * ``ssd_scan``        — Mamba-2 / RWKV-6 recurrence, VMEM-resident state.
  * ``hash_probe``      — graph-engine locate (VMEM-resident table).
  * ``frontier``        — BFS frontier expansion (a min proposer per destination:
    a pull scan over destination-sorted lanes in XLA, a scatter-min in the kernel).
  * ``compact``         — state-maintenance compaction (prefix-sum stream
    compaction + claim-round quadratic-probe placement).
"""
