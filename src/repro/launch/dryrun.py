import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

This proves the distribution config is coherent without hardware: the 16x16
single-pod mesh and the 2x16x16 multi-pod mesh must lower AND compile every
supported cell; ``memory_analysis`` proves the working set fits,
``cost_analysis`` + HLO collective parsing feed §Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch stablelm-1.6b \
      --shape train_4k --step train --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all --out results.json
"""

import argparse
import json
import time
import traceback

import jax

from repro.launch.hlo_analysis import analyze_collectives, analyze_dot_flops


def run_cell(
    arch: str,
    shape_name: str,
    step_kind: str,
    *,
    multi_pod: bool = False,
    unroll: bool = False,
    skiplora_mode: str = "full",
    strategy: str = "tp",
) -> dict:
    """Lower + compile one cell; return the §Dry-run / §Roofline record."""
    from repro.configs.registry import get_config
    from repro.core.lm_skiplora import SkipLoRAConfig
    from repro.launch.mesh import make_production_mesh
    from repro.launch.steps import build_cell
    from repro.models import blocks

    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    sl = SkipLoRAConfig(rank=16, mode=skiplora_mode)
    fn, args, in_sh, out_sh = build_cell(
        arch, shape_name, mesh, step_kind, skiplora=sl, strategy=strategy
    )

    with mesh:
        with blocks.scan_unroll_scope(unroll):
            jitted = (
                jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh)
                if out_sh is not None
                else jax.jit(fn, in_shardings=in_sh)
            )
            lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = analyze_collectives(hlo)
    dot_flops = analyze_dot_flops(hlo)

    rec = {
        "arch": arch,
        "shape": shape_name,
        "step": step_kind,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "strategy": strategy,
        "chips": int(mesh.devices.size),
        "unrolled": unroll,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        # Per-device numbers (XLA SPMD module == one device's program).
        "flops": float(cost.get("flops", 0.0)),
        "dot_flops": dot_flops,
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "collective_bytes": coll.total_bytes,
        "collective_count": coll.count,
        "collectives_per_op": coll.per_op_bytes,
    }
    for attr in (
        "temp_size_in_bytes",
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "generated_code_size_in_bytes",
    ):
        try:
            rec[attr] = int(getattr(mem, attr))
        except Exception:
            pass
    rec["memory_analysis"] = str(mem)
    return rec


def default_step_for(shape_name: str) -> str:
    return {
        "train_4k": "train",
        "prefill_32k": "prefill",
        "decode_32k": "decode",
        "long_500k": "decode",
    }[shape_name]


def main() -> None:
    from repro.configs.registry import list_archs
    from repro.launch.shapes import SHAPES, cell_supported

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--step", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true", help="run every supported cell")
    ap.add_argument("--skiplora-mode", default="full")
    ap.add_argument("--strategy", default="tp", choices=["tp", "fsdp", "ep"])
    ap.add_argument("--unroll", action="store_true", help="unroll period scans (slower compile; same analysis numbers)")
    ap.add_argument("--out", default=None, help="append JSON records here")
    args = ap.parse_args()

    cells: list[tuple[str, str, str]] = []
    if args.all:
        for a in list_archs():
            for s in SHAPES:
                ok, why = cell_supported(a, s)
                if not ok:
                    print(f"SKIP {a} x {s}: {why}")
                    continue
                cells.append((a, s, default_step_for(s)))
    else:
        assert args.arch and args.shape
        step = args.step or default_step_for(args.shape)
        cells.append((args.arch, args.shape, step))

    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    records = []
    for arch, shape, step in cells:
        for mp in meshes:
            tag = f"{arch} x {shape} x {step} x {'multi' if mp else 'single'}"
            try:
                rec = run_cell(
                    arch,
                    shape,
                    step,
                    multi_pod=mp,
                    unroll=args.unroll,
                    skiplora_mode=args.skiplora_mode,
                    strategy=args.strategy,
                )
                records.append(rec)
                print(
                    f"OK   {tag}: flops={rec['flops']:.3e} "
                    f"coll={rec['collective_bytes']:.3e}B "
                    f"compile={rec['compile_s']}s"
                )
                print("  memory:", rec["memory_analysis"].replace("\n", " | ")[:300])
            except Exception as e:
                print(f"FAIL {tag}: {type(e).__name__}: {e}")
                traceback.print_exc()
                records.append(
                    {"arch": arch, "shape": shape, "step": step,
                     "mesh": "2x16x16" if mp else "16x16", "error": str(e)}
                )
    if args.out:
        existing = []
        if os.path.exists(args.out):
            with open(args.out) as f:
                existing = json.load(f)
        with open(args.out, "w") as f:
            json.dump(existing + records, f, indent=1)
        print(f"wrote {len(records)} records to {args.out}")


if __name__ == "__main__":
    from repro.launch import enable_compile_cache

    # Every cell's program spans the forced production mesh.
    enable_compile_cache(jax.device_count())
    main()
