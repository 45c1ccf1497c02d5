"""Subprocess probe for tests/test_serving_sharded.py (slow lane).

Runs in its own interpreter so the parent pytest process can force an
8-device host mesh via XLA_FLAGS without contaminating its own jax
backend.  Asserts that tensor-parallel (2- and 4-shard) and 2-replica
engines reproduce the committed golden token streams bit-for-bit; with
``--vs-unsharded``, that 2- and 4-shard engines reproduce the unsharded
engine's streams, for the XLA and the fused (Pallas) backends; with
``--moe``, the same for an expert model, whose dropless grouped-matmul
kernel runs on replicated weights on every shard.
"""
import dataclasses
import json
import sys
from pathlib import Path

import jax
import numpy as np

from repro.configs import get_smoke_config
from repro.models import build_model
from repro.serving import ReplicatedEngine, Request, ServingEngine

GOLDEN = Path(__file__).parent / "golden"

CASES = [
    ("codeqwen-ssa-packed-paged", "codeqwen15_7b", "packed", "paged"),
    ("codeqwen-ssa-dense-slab", "codeqwen15_7b", "dense", "slab"),
    ("gemma2-ssa-packed-paged", "gemma2_9b", "packed", "paged"),
]
# expert models: no golden fixture, compared with the unsharded engine only
MOE_CASES = [
    ("mellum2-ssa-packed-paged", "mellum2_12b_a2_5b", "packed", "paged"),
]


def streams(engine):
    reqs = [
        Request(uid=0, prompt=np.array([3, 1, 4, 1, 5, 9, 2, 6], np.int32),
                max_new_tokens=5, seed=17),
        Request(uid=1, prompt=np.array([2, 7, 1, 8], np.int32),
                max_new_tokens=5, seed=23),
    ]
    for r in reqs:
        engine.submit(r)
    engine.run_until_done(max_ticks=100)
    return [list(map(int, r.out_tokens)) for r in reqs]


def build(arch, storage, layout, backend="auto"):
    cfg = get_smoke_config(arch)
    cfg = dataclasses.replace(
        cfg,
        attention=dataclasses.replace(
            cfg.attention, impl="ssa", spike_storage=storage,
            cache_layout=layout, backend=backend,
        ),
    )
    model = build_model(cfg)
    kw = dict(num_slots=2, max_seq=32)
    if layout == "paged":
        kw["page_size"] = 8
    return model, model.init(jax.random.PRNGKey(0)), kw


def vs_unsharded(cases=CASES):
    """TP engines against the unsharded engine in this same process, for
    the XLA backends and the Pallas kernels (run per head shard)."""
    for name, arch, storage, layout in cases:
        # the windowed gemma2 case adds no kernel path: XLA only there
        backends = ("xla",) if arch == "gemma2_9b" else ("xla", "fused")
        for backend in backends:
            model, params, kw = build(arch, storage, layout, backend)
            want = streams(ServingEngine(model, params, **kw))
            for shards in (2, 4):
                got = streams(ServingEngine(model, params,
                                            mesh_shards=shards, **kw))
                assert got == want, (name, backend, f"tp{shards}", got, want)
                print(name, backend, f"tp{shards} ok", flush=True)
    print("SHARDED_PROBE_OK")


def main():
    assert len(jax.devices()) >= 4, (
        f"probe needs >= 4 devices, got {len(jax.devices())}"
    )
    if sys.argv[1:] == ["--vs-unsharded"]:
        return vs_unsharded()
    if sys.argv[1:] == ["--moe"]:
        return vs_unsharded(MOE_CASES)
    for name, arch, storage, layout in CASES:
        with open(GOLDEN / f"{name}.json") as f:
            want = json.load(f)["streams"]
        model, params, kw = build(arch, storage, layout)
        for shards in (2, 4):
            got = streams(ServingEngine(model, params,
                                        mesh_shards=shards, **kw))
            assert got == want, (name, f"tp{shards}", got, want)
            print(name, f"tp{shards} ok", flush=True)
        got = streams(ReplicatedEngine(model, params, replicas=2, **kw))
        assert got == want, (name, "rep2", got, want)
        print(name, "rep2 ok", flush=True)
    print("SHARDED_PROBE_OK")


if __name__ == "__main__":
    sys.exit(main())
