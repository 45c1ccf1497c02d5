"""Subprocess probe: lower+compile smoke configs on a multi-device host mesh.

Run by test_distributed_lowering.py with
XLA_FLAGS=--xla_force_host_platform_device_count=8 so the main pytest
process keeps its single CPU device.  Exit code 0 = all probes compiled.
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ParallelConfig, ShapeConfig, TrainConfig, get_smoke_config
from repro.distributed.sharding import ShardingRules
from repro.launch.mesh import make_mesh
from repro.distributed.steps import (
    batch_pspecs,
    build_train_step,
    init_train_state,
    train_state_pspecs,
)
from repro.models import build_model


def probe(arch: str, impl: str | None = None):
    import dataclasses

    cfg = get_smoke_config(arch)
    if impl:
        cfg = dataclasses.replace(
            cfg, attention=dataclasses.replace(cfg.attention, impl=impl)
        )
    mesh = make_mesh((2, 4), ("data", "model"))
    rules = ShardingRules(mesh, batch_shardable=True, seq_parallel=True)
    parallel = ParallelConfig(remat="dots")
    model = build_model(cfg)
    train_cfg = TrainConfig()
    step_fn, opt = build_train_step(model, train_cfg, parallel, rules)
    with mesh:
        state = init_train_state(model, jax.random.PRNGKey(0), opt, parallel)
        state_shapes = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state
        )
        specs = train_state_pspecs(state_shapes, rules, parallel)
        shape = ShapeConfig("probe", 32, 8, "train")
        in_specs = model.input_specs(shape)
        bspecs = batch_pspecs(in_specs, rules)
        ns = lambda t: jax.tree.map(
            lambda sp: NamedSharding(mesh, sp), t, is_leaf=lambda x: isinstance(x, P)
        )
        jitted = jax.jit(
            step_fn, in_shardings=(ns(specs), ns(bspecs)), out_shardings=(ns(specs), None)
        )
        lowered = jitted.lower(state_shapes, in_specs)
        compiled = lowered.compile()

        # numerically run one real step on the 8-device mesh
        batch = {}
        for name, spec in in_specs.items():
            if spec.dtype == jnp.int32:
                if name == "positions":
                    arr = jnp.broadcast_to(jnp.arange(spec.shape[-1]), spec.shape)
                else:
                    arr = jax.random.randint(
                        jax.random.PRNGKey(1), spec.shape, 0, cfg.vocab_size
                    )
            else:
                arr = jax.random.normal(jax.random.PRNGKey(2), spec.shape).astype(spec.dtype)
            batch[name] = jax.device_put(arr, NamedSharding(mesh, bspecs[name]))
        state = jax.tree.map(
            lambda x, sp: jax.device_put(x, NamedSharding(mesh, sp)), state, specs,
            is_leaf=lambda x: isinstance(x, jax.Array),
        )
        new_state, metrics = jitted(state, batch)
        loss = float(metrics["loss"])
        assert np.isfinite(loss), f"{arch}: loss {loss}"
        print(f"probe {arch} impl={impl or 'default'}: loss {loss:.4f} OK", flush=True)


if __name__ == "__main__":
    archs = sys.argv[1:] or ["mixtral_8x7b", "codeqwen15_7b"]
    for a in archs:
        impl = None
        if ":" in a:
            a, impl = a.split(":")
        probe(a, impl)
    print("ALL_PROBES_OK")
