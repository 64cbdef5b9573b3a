"""Distributed-correctness tests.

These run in a SUBPROCESS with XLA_FLAGS forcing 8 host devices (the flag
must never leak into the main test process — smoke tests see 1 device).
The subprocess asserts:
  * sharded loss == unsharded loss (dense + moe smoke models, (2,4) mesh)
  * expert-parallel shard_map MoE == single-device MoE
  * a reduced multi-pod (2,2,2) dry-run lower+compile succeeds
"""
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_smoke_config
    from repro.models import build_model
    from repro.models.api import cross_entropy
    from repro.launch.mesh import make_mesh
    from repro.launch.shardings import make_policy
    from repro.config import ShapeConfig

    assert len(jax.devices()) == 8
    mesh = make_mesh((2, 4), ("data", "model"))

    for arch in ("qwen3-32b", "qwen3-moe-235b-a22b"):
        cfg = get_smoke_config(arch)
        # make dims divisible by the tiny mesh: heads 8 % 4 == 0, vocab 256
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        B, T = 4, 32
        tokens = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0, cfg.vocab)
        batch = {"tokens": tokens, "labels": tokens}
        ref = float(cross_entropy(cfg, model.apply(params, batch)[0], batch))

        shape = ShapeConfig("t", "train", T, B)
        policy = make_policy(mesh, cfg, shape, fsdp=False)
        policy.dp_only = False  # force TP for the test despite tiny params
        p_sh = policy.params_sharding(params)
        b_sh = policy.batch_sharding(batch)

        def loss_fn(p, b):
            logits, _ = model.apply(p, b, policy=policy)
            return cross_entropy(cfg, logits, b)

        with mesh:
            jl = jax.jit(loss_fn, in_shardings=(p_sh, b_sh))
            sharded = float(jl(jax.device_put(params, p_sh),
                               jax.device_put(batch, b_sh)))
        rel = abs(sharded - ref) / max(abs(ref), 1e-9)
        assert rel < 2e-2, f"{arch}: sharded {sharded} vs ref {ref}"
        print(f"OK {arch}: sharded loss {sharded:.4f} == ref {ref:.4f}")

    # multi-pod reduced dry-run: (2,2,2) mesh lower+compile train_step
    mesh3 = make_mesh((2, 2, 2), ("pod", "data", "model"))
    cfg = get_smoke_config("yi-9b")
    model = build_model(cfg)
    from repro.launch.steps import make_train_step
    from repro.optim import OptConfig, adamw_init
    shape = ShapeConfig("t", "train", 32, 8)
    policy = make_policy(mesh3, cfg, shape, fsdp=False)
    step = make_train_step(cfg, policy, OptConfig(), remat="full")
    ps = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    os_ = jax.eval_shape(lambda p: adamw_init(OptConfig(), p), ps)
    bs = {"tokens": jax.ShapeDtypeStruct((8, 32), jnp.int32),
          "labels": jax.ShapeDtypeStruct((8, 32), jnp.int32)}
    p_sh = policy.params_sharding(ps)
    with mesh3:
        c = jax.jit(step, in_shardings=(p_sh, policy.opt_sharding(p_sh),
                                        policy.batch_sharding(bs))
                    ).lower(ps, os_, bs).compile()
    assert c.memory_analysis() is not None
    print("OK multi-pod smoke compile")
""")


@pytest.mark.slow
def test_distributed_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = env.get("PYTHONPATH", "src")
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, env=env, timeout=900)
    assert r.returncode == 0, f"STDOUT:{r.stdout[-3000:]}\nERR:{r.stderr[-3000:]}"
    assert r.stdout.count("OK") == 3
