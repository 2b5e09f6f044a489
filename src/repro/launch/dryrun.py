"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out DIR]

Proves the distribution config is coherent without hardware: compile must
succeed, ``memory_analysis()`` must fit the 16 GiB/chip HBM budget, and
``cost_analysis()`` + the HLO collective sum feed §Roofline.
"""

# The VERY FIRST lines, before ANY other import (jax locks device count on
# first init): give the host platform 512 placeholder devices.
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

import argparse      # noqa: E402
import gzip          # noqa: E402
import json          # noqa: E402
import re            # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402

from repro.launch import hloparse  # noqa: E402

from repro.configs import ARCH_NAMES, SHAPES, cell_is_runnable, get_config  # noqa: E402
from repro.launch import steps as S  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402

# ---------------------------------------------------------------------------
# collective-bytes extraction (cost_analysis has no collective term)
# ---------------------------------------------------------------------------

_COLLECTIVE_RE = re.compile(
    r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\b"
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
}


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str):
    """Sum result-shape bytes of every collective op, by kind.

    The result type (right after ``=``) counts gathered bytes for
    all-gather and scattered bytes for reduce-scatter — a consistent
    per-device traffic proxy.  NOTE: ops inside while/scan bodies appear
    once in the HLO; execution counts are restored analytically by the
    roofline calculator (benchmarks/roofline.py), which knows each scan's
    trip count.
    """
    out = {}
    count = {}
    for line in hlo_text.splitlines():
        m = _COLLECTIVE_RE.search(line)
        if m is None or "=" not in line:
            continue
        kind = m.group(1)
        if m.group(0).endswith("-done"):
            continue  # avoid double count of async start/done pairs
        rhs = line.split("=", 1)[1]
        sm = _SHAPE_RE.search(rhs)
        if sm is None:
            continue
        b = _shape_bytes(sm.group(0))
        # tuple results (e.g. fused all-gather of several operands): sum all
        # shapes before the op name token
        op_pos = rhs.find(m.group(0))
        b = _shape_bytes(rhs[:op_pos]) if op_pos > 0 else b
        out[kind] = out.get(kind, 0) + b
        count[kind] = count.get(kind, 0) + 1
    return out, count


# ---------------------------------------------------------------------------
# one cell
# ---------------------------------------------------------------------------

def run_cell(arch: str, shape: str, *, multi_pod: bool, verbose: bool = True,
             hlo_dir: str = None, step_overrides: dict = None):
    cfg = get_config(arch)
    if not cell_is_runnable(cfg, shape):
        return {
            "arch": arch, "shape": shape, "multi_pod": multi_pod,
            "status": "skipped",
            "reason": "long_500k requires sub-quadratic attention (DESIGN.md §5)",
        }

    mesh = make_production_mesh(multi_pod=multi_pod)
    kind = SHAPES[shape]["kind"]
    t0 = time.time()

    step_overrides = step_overrides or {}
    with jax.default_device(jax.devices("cpu")[0]):
        if kind == "train":
            fn, model, run = S.build_train_step(cfg, multi_pod=multi_pod,
                                                **step_overrides)
        elif kind == "prefill":
            fn, model, run = S.build_prefill_step(cfg, multi_pod=multi_pod,
                                                  **step_overrides)
        else:
            fn, model, run = S.build_decode_step(cfg, multi_pod=multi_pod,
                                                 **step_overrides)

        specs = S.input_specs(cfg, shape, mesh, multi_pod=multi_pod)

        with jax.set_mesh(mesh):
            # NOTE donation was tried here (params/opt for train, cache for
            # decode) to mirror the real loop; the CPU backend's buffer
            # assignment got *worse* (+3.7 GiB at 104B train), so the
            # dry-run keeps the donation-free program and the budget table
            # documents it as the conservative bound.
            lowered = jax.jit(fn).lower(**specs)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo_text = compiled.as_text()
        coll, coll_n = collective_bytes(hlo_text)
        # execution-weighted (while bodies × trip count) — see hloparse
        exec_sum = hloparse.summarize(hlo_text)
        if hlo_dir is not None:
            os.makedirs(hlo_dir, exist_ok=True)
            tag = f"{arch}__{shape}__{'mp' if multi_pod else 'sp'}"
            with gzip.open(os.path.join(hlo_dir, tag + ".hlo.gz"), "wt") as f:
                f.write(hlo_text)

    n_dev = 512 if multi_pod else 256
    result = {
        "arch": arch,
        "shape": shape,
        "multi_pod": multi_pod,
        "status": "ok",
        "n_devices": n_dev,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "collective_bytes": coll,
        "collective_counts": coll_n,
        # while-body-once undercount corrected (tests/test_hloparse.py):
        "exec": exec_sum,
        "memory": {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "alias_bytes": int(mem.alias_size_in_bytes),
        },
    }
    if verbose:
        per_dev_gib = (
            result["memory"]["argument_bytes"]
            + result["memory"]["temp_bytes"]
        ) / 2**30
        print(
            f"[{arch} × {shape} × {'2pods' if multi_pod else '1pod'}] OK "
            f"lower {t_lower:.0f}s compile {t_compile:.0f}s | "
            f"flops {result['flops']:.3e} bytes {result['bytes_accessed']:.3e} | "
            f"coll {sum(coll.values()):.3e}B | mem/dev {per_dev_gib:.2f} GiB"
        )
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--hlo-out", default="results/hlo")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    cells = []
    if args.all:
        for arch in ARCH_NAMES:
            for shape in SHAPES:
                cells.append((arch, shape))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells.append((args.arch, args.shape))

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    failures = 0
    for multi_pod in meshes:
        for arch, shape in cells:
            tag = f"{arch}__{shape}__{'mp' if multi_pod else 'sp'}"
            path = os.path.join(args.out, tag + ".json")
            if os.path.exists(path) and not args.force:
                print(f"[{tag}] cached")
                continue
            try:
                result = run_cell(arch, shape, multi_pod=multi_pod,
                                  hlo_dir=args.hlo_out)
            except Exception as e:  # noqa: BLE001 — record and continue
                traceback.print_exc()
                result = {
                    "arch": arch, "shape": shape, "multi_pod": multi_pod,
                    "status": "error", "error": f"{type(e).__name__}: {e}",
                }
                failures += 1
            with open(path, "w") as f:
                json.dump(result, f, indent=2)
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
