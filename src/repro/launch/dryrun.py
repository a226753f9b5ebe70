import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count at first init). Everything below may import jax.

"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh)
combination on placeholder devices and extract the roofline terms.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun \
      --arch all --shape all --mesh single --out reports/dryrun

Per combo this prints/records:
  * compiled.memory_analysis()  (proves per-device footprint)
  * compiled.cost_analysis()    (HLO FLOPs / bytes for the roofline)
  * collective bytes by op type (parsed from the post-SPMD HLO)
  * the three roofline terms (compute / memory / collective, seconds)
"""

import argparse
import dataclasses
import json
import re
import sys
import time
import traceback

import jax
import numpy as np

from repro.configs import get_arch_config, list_archs
from repro.configs.shapes import SHAPES, get_shape
from repro.launch.input_specs import build_specs
from repro.launch.mesh import (
    HBM_BW, ICI_BW, PEAK_FLOPS, make_production_mesh,
)
from repro.configs.base import param_count

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s+(?:\([^)]*\)|(\w+)\[([\d,]*)\][^ ]*)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
)
_TUPLE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _nbytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str) -> dict:
    """Sum result bytes of every collective op in a (post-SPMD) HLO module."""
    out: dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        op = m.group(3)
        if m.group(1):  # simple result
            b = _nbytes(m.group(1), m.group(2))
        else:           # tuple result: sum elements
            head = line.split(f" {op}(")[0]
            b = sum(_nbytes(d, s) for d, s in _TUPLE_RE.findall(head))
        out[op] = out.get(op, 0) + b
    out["total"] = sum(v for k, v in out.items() if k != "total")
    return out


def roofline_terms(flops_per_dev, bytes_per_dev, coll_bytes_per_dev,
                   n_links: int = 4) -> dict:
    return dict(
        compute_s=flops_per_dev / PEAK_FLOPS,
        memory_s=bytes_per_dev / HBM_BW,
        collective_s=coll_bytes_per_dev / (ICI_BW * n_links),
    )


def run_one(arch: str, shape_name: str, *, multi_pod: bool, mode=None,
            gossip_overrides=None, arch_overrides=None, verbose=True,
            opts=None) -> dict:
    from repro.launch.input_specs import PerfOpts
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = int(np.prod(list(mesh.shape.values())))
    cfg = get_arch_config(arch, **(arch_overrides or {}))
    shape = get_shape(shape_name)

    t0 = time.time()
    spec = build_specs(cfg, shape, mesh, mode=mode,
                       gossip_overrides=gossip_overrides,
                       opts=opts if opts is not None else PerfOpts())
    step = spec.meta["step"]

    with jax.set_mesh(mesh):
        jitted = jax.jit(
            step,
            in_shardings=spec.in_specs,
            out_shardings=spec.out_specs,
            donate_argnums=spec.donate,
        )
        lowered = jitted.lower(*spec.abstract_args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):  # jax < 0.5: one dict per computation
        cost = cost[0] if cost else {}
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)

    # NOTE: on the CPU backend with scan-over-layers, cost_analysis counts
    # while-loop bodies ONCE (not x trip count), so the raw numbers below
    # undercount by ~n_layers; the analytic model is the primary roofline
    # source (EXPERIMENTS.md §Dry-run caveat). Both are recorded.
    xla_flops_dev = float(cost.get("flops", 0.0))
    xla_bytes_dev = float(cost.get("bytes accessed", 0.0))
    coll_dev = float(coll.get("total", 0))

    from repro.launch.roofline import analytic_roofline
    ana = analytic_roofline(
        cfg, shape, dict(mesh.shape), mode=spec.mode,
        window_override=spec.meta.get("window"),
    )

    n_params = param_count(cfg)
    n_active = param_count(cfg, active_only=True)
    tokens = shape.global_batch * (shape.seq_len if spec.step_kind != "decode" else 1)
    if spec.step_kind == "train":
        model_flops = 6 * n_active * tokens
    else:
        model_flops = 2 * n_active * tokens
    useful = model_flops / max(ana.flops_dev * n_dev, 1.0)

    rec = dict(
        arch=arch, shape=shape_name, mesh="multi" if multi_pod else "single",
        mode=spec.mode, step_kind=spec.step_kind, n_devices=n_dev,
        lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
        bytes_per_device=getattr(mem, "temp_size_in_bytes", None),
        argument_bytes=getattr(mem, "argument_size_in_bytes", None),
        output_bytes=getattr(mem, "output_size_in_bytes", None),
        xla_raw=dict(
            flops_per_device=xla_flops_dev, bytes_per_device=xla_bytes_dev,
            collective_bytes=coll,
            caveat="while bodies counted once; see EXPERIMENTS.md",
        ),
        roofline=ana.as_dict(), dominant=ana.dominant,
        model_flops=model_flops, useful_flops_ratio=useful,
        n_params=n_params, n_params_active=n_active,
        meta={k: v for k, v in spec.meta.items()
              if isinstance(v, (int, str, float)) or v is None},
    )
    if verbose:
        print(
            f"[{rec['mesh']}] {arch} x {shape_name} ({spec.mode}): "
            f"lower {t_lower:.0f}s compile {t_compile:.0f}s | "
            f"temp/dev {(rec['bytes_per_device'] or 0)/1e9:.2f} GB | "
            f"compute {ana.compute_s*1e3:.2f}ms mem {ana.memory_s*1e3:.2f}ms "
            f"coll {ana.collective_s*1e3:.2f}ms | dom {ana.dominant} | "
            f"useful {useful:.2f}"
        )
        sys.stdout.flush()
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--mode", default=None, help="force train mode")
    ap.add_argument("--out", default="reports/dryrun")
    ap.add_argument("--stop-on-error", action="store_true")
    ap.add_argument("--baseline", action="store_true",
                    help="disable the §Perf optimizations (naive config)")
    args = ap.parse_args(argv)
    from repro.launch.input_specs import PerfOpts
    opts = PerfOpts.baseline() if args.baseline else PerfOpts()

    archs = list_archs() if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    os.makedirs(args.out, exist_ok=True)
    failures = []
    for multi in meshes:
        for arch in archs:
            for shape in shapes:
                tag = f"{'multi' if multi else 'single'}_{arch}_{shape}"
                path = os.path.join(args.out, tag + ".json")
                if os.path.exists(path):
                    print(f"skip {tag} (exists)")
                    continue
                try:
                    rec = run_one(arch, shape, multi_pod=multi,
                                  mode=args.mode, opts=opts)
                    with open(path, "w") as f:
                        json.dump(rec, f, indent=1)
                except Exception as e:  # noqa: BLE001
                    failures.append((tag, repr(e)))
                    print(f"FAIL {tag}: {e}")
                    traceback.print_exc()
                    if args.stop_on_error:
                        raise
    print(f"\ndone; {len(failures)} failures")
    for tag, err in failures:
        print(" ", tag, err[:200])
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
