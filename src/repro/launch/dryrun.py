import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"  # a dry run never takes the chip

"""Multi-pod dry-run: prove the distribution config is coherent.

For every (arch x shape x mesh) cell: build the production mesh, construct
ShapeDtypeStruct inputs (never allocating), ``jit(...).lower().compile()``
the step the shape's kind dictates, and record memory_analysis /
cost_analysis / the collective schedule.

Usage:
    python -m repro.launch.dryrun --arch yi-9b --shape train_4k --mesh single
    python -m repro.launch.dryrun --all --mesh both      # full sweep
"""
import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.config import SHAPES, cells  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.launch.hlo_analysis import collective_bytes, collective_summary  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.shardings import make_policy  # noqa: E402
from repro.launch.steps import (  # noqa: E402
    make_decode_step, make_prefill_step, make_train_step)
from repro.models.api import build_model, input_specs  # noqa: E402
from repro.optim import OptConfig, adamw_init  # noqa: E402

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "benchmarks", "results", "dryrun")

# Per-arch training memory knobs (DESIGN.md §6): the >=100B MoE cells use
# bf16 optimizer state; the 1T model additionally host-offloads it (the
# paper's sysRAM tier at pod scale).
ARCH_OVERRIDES = {
    "kimi-k2-1t-a32b": {"state_dtype": "bfloat16", "offload_opt": True},
    "qwen3-moe-235b-a22b": {"state_dtype": "bfloat16", "offload_opt": False},
}


def lower_cell(arch: str, shape_name: str, multi_pod: bool):
    cfg = get_config(arch)
    if os.environ.get("REPRO_EXPERT_QUANT"):  # perf-iteration C2 knob
        cfg = cfg.replace(expert_quant=os.environ["REPRO_EXPERT_QUANT"])
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    ov = ARCH_OVERRIDES.get(arch, {})
    policy = make_policy(mesh, cfg, shape,
                         offload_opt=ov.get("offload_opt", False))
    model = build_model(cfg)
    specs = input_specs(cfg, shape)
    params_struct = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params_sh = policy.params_sharding(params_struct)
    batch_sh = policy.batch_sharding(specs["batch"])

    if shape.kind == "train":
        oc = OptConfig(state_dtype=ov.get("state_dtype", "float32"))
        mb = int(os.environ.get("REPRO_MICROBATCHES", "1"))
        remat = os.environ.get("REPRO_REMAT", "full")  # perf knob G2
        step = make_train_step(cfg, policy, oc, remat=remat, microbatches=mb)
        opt_struct = jax.eval_shape(lambda p: adamw_init(oc, p), params_struct)
        opt_sh = policy.opt_sharding(params_sh)
        # XLA SPMD RET_CHECKs rank-1 device-placement annotations when
        # explicit out_shardings mix memory kinds -> let outputs propagate.
        out_sh = None if policy.offload_opt else (params_sh, opt_sh, None)
        jitted = jax.jit(step,
                         in_shardings=(params_sh, opt_sh, batch_sh),
                         out_shardings=out_sh,
                         donate_argnums=(0, 1))
        args = (params_struct, opt_struct, specs["batch"])
    elif shape.kind == "prefill":
        step = make_prefill_step(cfg, policy)
        cache_sh = policy.cache_sharding(specs["cache"])
        jitted = jax.jit(step,
                         in_shardings=(params_sh, batch_sh, cache_sh),
                         out_shardings=(None, cache_sh),
                         donate_argnums=(2,))
        args = (params_struct, specs["batch"], specs["cache"])
    else:  # decode
        step = make_decode_step(cfg, policy)
        cache_sh = policy.cache_sharding(specs["cache"])
        jitted = jax.jit(step,
                         in_shardings=(params_sh, batch_sh, cache_sh,
                                       policy.scalar_sharding()),
                         out_shardings=(None, cache_sh),
                         donate_argnums=(2,))
        args = (params_struct, specs["batch"], specs["cache"], specs["pos"])

    with mesh:
        lowered = jitted.lower(*args)
        compiled = lowered.compile()
    return cfg, shape, mesh, lowered, compiled


def run_cell(arch: str, shape_name: str, multi_pod: bool, save: bool = True):
    t0 = time.time()
    cfg, shape, mesh, lowered, compiled = lower_cell(arch, shape_name, multi_pod)
    compile_s = time.time() - t0
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    n_chips = mesh.devices.size
    coll = collective_bytes(hlo, while_trips=cfg.n_layers)
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_chips": int(n_chips),
        "kind": shape.kind,
        "compile_seconds": round(compile_s, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "host_argument_bytes": mem.host_argument_size_in_bytes,
            "host_temp_bytes": mem.host_temp_size_in_bytes,
            "per_chip_peak_bytes": mem.argument_size_in_bytes
            + mem.temp_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes,
        },
        "hlo_flops_per_chip": cost.get("flops", 0.0),
        "hlo_bytes_per_chip": cost.get("bytes accessed", 0.0),
        "collectives": {
            "total_traffic_bytes": coll["total_bytes"],
            "by_kind": coll["by_kind"],
            "n_ops": len(coll["per_op"]),
            "note": f"while-body collectives multiplied by n_layers={cfg.n_layers}",
        },
    }
    print(f"[dryrun] {arch} x {shape_name} x {result['mesh']}: "
          f"compile {compile_s:.1f}s, "
          f"args/chip {mem.argument_size_in_bytes/1e9:.2f}GB, "
          f"temp/chip {mem.temp_size_in_bytes/1e9:.2f}GB, "
          f"flops/chip {result['hlo_flops_per_chip']:.3e}, "
          f"{collective_summary(hlo, cfg.n_layers)}")
    if save:
        os.makedirs(RESULTS_DIR, exist_ok=True)
        fn = os.path.join(RESULTS_DIR,
                          f"{result['mesh']}__{arch}__{shape_name}.json")
        with open(fn, "w") as f:
            json.dump(result, f, indent=1)
    del lowered, compiled
    gc.collect()
    return result


def sweep(mesh_mode: str, only_failed: bool = False):
    """Run every cell in a subprocess (isolates compiles; survives OOM)."""
    os.makedirs(RESULTS_DIR, exist_ok=True)
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[mesh_mode]
    failures = []
    for arch, shape_name in cells():
        for multi in meshes:
            tag = f"{'2x16x16' if multi else '16x16'}__{arch}__{shape_name}"
            out = os.path.join(RESULTS_DIR, tag + ".json")
            if only_failed and os.path.exists(out):
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape_name,
                   "--mesh", "multi" if multi else "single"]
            r = subprocess.run(cmd, capture_output=True, text=True,
                               env={**os.environ,
                                    "PYTHONPATH": os.environ.get("PYTHONPATH", "src")})
            if r.returncode != 0:
                failures.append(tag)
                with open(os.path.join(RESULTS_DIR, tag + ".FAILED"), "w") as f:
                    f.write(r.stdout[-4000:] + "\n" + r.stderr[-8000:])
                print(f"[dryrun] FAIL {tag} (log: {tag}.FAILED)")
            else:
                print(r.stdout.strip().splitlines()[-1] if r.stdout.strip() else tag)
    print(f"[dryrun] sweep done; {len(failures)} failures: {failures}")
    return failures


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--only-failed", action="store_true")
    args = ap.parse_args()
    if args.all:
        failures = sweep(args.mesh, args.only_failed)
        sys.exit(1 if failures else 0)
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    for multi in meshes:
        try:
            run_cell(args.arch, args.shape, multi)
        except Exception:
            traceback.print_exc()
            sys.exit(1)


if __name__ == "__main__":
    main()
