"""Train an LM end-to-end with the fault-tolerant driver: checkpointing,
restart-on-failure, straggler watch, deterministic data replay.

Default is a quick CPU-sized run; ``--preset 100m --steps 300`` is the
full ~100M-parameter configuration (same code path, longer wall-clock).

    PYTHONPATH=src python examples/train_lm.py [--steps 60]
"""
import argparse

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.compile_cache import enable_compile_cache
from repro.configs import get_smoke_config
from repro.data import DataPipeline
from repro.launch.steps import make_train_step
from repro.models import build_model
from repro.optim import OptConfig, adamw_init
from repro.runtime import FaultInjector, TrainDriver


def preset_cfg(name):
    if name == "100m":
        return ModelConfig(
            name="lm-100m", family="dense", n_layers=12, d_model=768,
            n_heads=12, n_kv_heads=4, d_ff=2048, vocab=32000, head_dim=64,
            mlp="swiglu", pos="rope")
    return get_smoke_config("yi-9b")


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="smoke", choices=["smoke", "100m"])
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--inject-fault", type=int, default=25,
                    help="step at which to inject a failure (-1: none)")
    args = ap.parse_args()

    cfg = preset_cfg(args.preset)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    n = sum(x.size for x in jax.tree.leaves(params))
    print(f"model {cfg.name}: {n/1e6:.1f}M params")
    oc = OptConfig(lr=3e-3, warmup_steps=10, total_steps=args.steps,
                   weight_decay=0.0)
    jitted = jax.jit(make_train_step(cfg, oc=oc, remat="none"))

    def step_fn(state, batch):
        b = {k: jnp.asarray(v) for k, v in batch.items()}
        p, o, metrics = jitted(state["params"], state["opt"], b)
        return {"params": p, "opt": o}, metrics

    pipe = DataPipeline(cfg, args.seq, args.batch, seed=0,
                        process_index=0, process_count=1)
    faults = FaultInjector([args.inject_fault] if args.inject_fault >= 0 else [])
    drv = TrainDriver(step_fn, {"params": params, "opt": adamw_init(oc, params)},
                      pipe, args.ckpt_dir, ckpt_every=20,
                      fault_injector=faults)
    log = drv.run(args.steps)
    for i in range(0, len(log), max(1, len(log) // 10)):
        print(f"step {i:4d}: loss {log[i]['loss']:.4f} "
              f"lr {log[i]['lr']:.2e} gnorm {log[i]['grad_norm']:.2f}")
    print(f"final loss {log[-1]['loss']:.4f} (first {log[0]['loss']:.4f})")
    print(f"runtime events: {drv.events}")


if __name__ == "__main__":
    main()
