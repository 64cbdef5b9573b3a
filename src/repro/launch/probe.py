import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"  # a dry run never takes the chip
os.environ["REPRO_SCAN_UNROLL"] = "1"
os.environ["REPRO_FORCE_REF_ATTN"] = "1"

"""Per-layer roofline probe (DESIGN.md §4).

XLA cost_analysis counts a while body once, so the full scanned model
undercounts FLOPs by ~n_layers. This probe lowers the SAME step at two
reduced depths with layer scans UNROLLED and attention in scan-free
reference form, then reconstructs:

    per_layer = (cost(L2) - cost(L1)) / (L2 - L1)
    total     = cost(L1) - per_layer * L1  +  per_layer * n_layers

Exact for matmul-dominated graphs; validated against a fully-unrolled small
model in tests. Collectives come out exact too (no loops left).

Usage: python -m repro.launch.probe --arch yi-9b --shape train_4k
"""
import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402

from repro.config import SHAPES, cells  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.launch.dryrun import RESULTS_DIR, lower_cell  # noqa: E402
from repro.launch.hlo_analysis import collective_bytes  # noqa: E402

PROBE_DIR = os.path.join(RESULTS_DIR, "..", "probe")


def depth_pair(cfg):
    """Two reduced depths whose difference isolates one layer (or group)."""
    if cfg.family == "hybrid":
        per = cfg.shared_attn_every
        return per, 2 * per, cfg.n_layers / per  # group-granular
    if cfg.family == "ssm":
        return 2, 4, cfg.n_layers / 2  # pair-granular
    return 1, 2, float(cfg.n_layers)


def _cost_at_depth(arch, shape_name, depth):
    import repro.configs as cfgs

    cfg = get_config(arch)
    cfg_d = cfg.replace(n_layers=depth)
    # monkeypatch get_config so lower_cell sees the reduced depth
    orig = cfgs.get_config
    cfgs.get_config = lambda a: cfg_d if a == arch else orig(a)
    import repro.launch.dryrun as dr
    orig_dr = dr.get_config
    dr.get_config = cfgs.get_config
    try:
        _, shape, mesh, lowered, compiled = lower_cell(arch, shape_name,
                                                       multi_pod=False)
        cost = compiled.cost_analysis() or {}
        coll = collective_bytes(compiled.as_text(), while_trips=1)
        out = {"flops": cost.get("flops", 0.0),
               "bytes": cost.get("bytes accessed", 0.0),
               "coll": coll["total_bytes"],
               "coll_by_kind": coll["by_kind"]}
        del lowered, compiled
        gc.collect()
        return out
    finally:
        cfgs.get_config = orig
        dr.get_config = orig_dr


def probe_cell(arch, shape_name, save=True):
    cfg = get_config(arch)
    d1, d2, n_units = depth_pair(cfg)
    c1 = _cost_at_depth(arch, shape_name, d1)
    c2 = _cost_at_depth(arch, shape_name, d2)
    out = {"arch": arch, "shape": shape_name, "mesh": "16x16",
           "depths": [d1, d2], "n_units": n_units}
    n_layers_eff = n_units * d1
    for k in ("flops", "bytes", "coll"):
        per_layer = (c2[k] - c1[k]) / (d2 - d1)
        # XLA occasionally partitions the depth-1 graph with MORE collective
        # traffic than depth-2 (different sharding choices); these totals
        # are monotone in depth, so clamp the extrapolation.
        per_layer = max(per_layer, 0.0)
        fixed = max(c1[k] - per_layer * d1, 0.0)
        out[k] = max(fixed + per_layer * n_layers_eff, c2[k])
        out[f"{k}_fixed"] = fixed
        out[f"{k}_per_layer"] = per_layer
    out["coll_by_kind"] = {k: (c2["coll_by_kind"].get(k, 0.0)
                               - c1["coll_by_kind"].get(k, 0.0))
                           / (d2 - d1) * n_units * d1
                           + c1["coll_by_kind"].get(k, 0.0)
                           for k in set(c1["coll_by_kind"]) | set(c2["coll_by_kind"])}
    print(f"[probe] {arch} x {shape_name}: flops/chip {out['flops']:.3e}, "
          f"bytes/chip {out['bytes']:.3e}, coll/chip {out['coll']/1e6:.1f}MB")
    if save:
        os.makedirs(PROBE_DIR, exist_ok=True)
        with open(os.path.join(PROBE_DIR, f"{arch}__{shape_name}.json"), "w") as f:
            json.dump(out, f, indent=1)
    return out


def sweep(only_failed=False):
    os.makedirs(PROBE_DIR, exist_ok=True)
    failures = []
    for arch, shape_name in cells():
        tag = f"{arch}__{shape_name}"
        fn = os.path.join(PROBE_DIR, tag + ".json")
        if only_failed and os.path.exists(fn):
            continue
        cmd = [sys.executable, "-m", "repro.launch.probe",
               "--arch", arch, "--shape", shape_name]
        r = subprocess.run(cmd, capture_output=True, text=True,
                           env={**os.environ,
                                "PYTHONPATH": os.environ.get("PYTHONPATH", "src")})
        if r.returncode != 0:
            failures.append(tag)
            with open(os.path.join(PROBE_DIR, tag + ".FAILED"), "w") as f:
                f.write(r.stdout[-3000:] + "\n" + r.stderr[-8000:])
            print(f"[probe] FAIL {tag}")
        else:
            print(r.stdout.strip().splitlines()[-1] if r.stdout.strip() else tag)
    print(f"[probe] sweep done; {len(failures)} failures: {failures}")
    return failures


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--only-failed", action="store_true")
    args = ap.parse_args()
    if args.all:
        sys.exit(1 if sweep(args.only_failed) else 0)
    try:
        probe_cell(args.arch, args.shape)
    except Exception:
        traceback.print_exc()
        sys.exit(1)


if __name__ == "__main__":
    main()
