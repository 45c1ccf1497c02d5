"""On-chip smoke test: the serving path at published widths on a TPU.

Run from the repository root on a machine with a TPU:

    python chip_smoke.py                # one chip: LM phase + ViT phase
    python chip_smoke.py --four-chips   # four chips: 4-way head-sharded LM
                                        # engine vs the one-chip engine

Every phase runs in this one process (no subprocess) through the calls a
user makes (``build_model`` -> ``ServingEngine.submit`` / ``step``), with
random weights drawn from ``--seed``:

* LM: codeqwen1.5-7b at every published width (d_model 4096, 32 x 128
  heads, 32 kv heads, d_ff 13440, vocab 92416) with the depth cut to
  ``LM_LAYERS``; SSA attention over the packed, paged KV cache.  The
  requests are served with the fused Pallas kernels (``backend=auto``),
  then with ``backend=xla`` on the same params; RNG contract v2 makes the
  two bit-identical, so the token streams must be equal.
* ViT: spiking-vit-small at its published size (6 layers, d 384,
  8 x 48 heads, T 10) classifying a burst of event streams through the
  paged engine; fused and XLA classes must be equal.

Info lines go to stdout as the phases run (wall-clock times there include
host work and are not device metrics).  The last line is one JSON object,
``{"ok": true, "device": {...}}``.  Without a TPU, or when a phase fails,
the script exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# codeqwen1.5-7b is 32 layers; in bf16 that is ~16.4 GB of weights, more
# than one v5e holds.  At 16 layers the weights are ~8.3 GiB, the packed
# paged cache for 8 x 2048 rows ~1.1 GiB, and the decode step's
# temporaries over 1024 cached rows ~3.6 GiB (XLA memory analysis for
# v5e), so the step peaks near 14.2 GiB of the chip's 16 GiB.
LM_LAYERS = 16
LM_SLOTS = 8
LM_MAX_SEQ = 2048
LM_NEW_TOKENS = 32
# Prompts stay below 1024 - LM_NEW_TOKENS tokens so a request never spans
# more than 64 pages: the next block-table bucket (128 pages) raises the
# decode temporaries to ~4.8 GiB, past what the chip has left.
LM_PROMPT_LENS = (128, 990)
LM_REQUESTS = 8
# a drained burst takes about one tick per prefill chunk plus one per token
LM_MAX_TICKS = 20 * LM_REQUESTS * (LM_MAX_SEQ // 16)
VIT_REQUESTS = 32
VIT_SLOTS = 8


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def info(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def check_device():
    """The first device must be a TPU, and Pallas must not interpret."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"no TPU: JAX reports platform {dev.platform!r} "
             f"({len(devices)} device(s)); this smoke test never falls back")
    try:
        from repro.attention import default_interpret
    except ImportError as e:
        fail(f"cannot import repro ({e}); run from the repository root")
    if default_interpret():
        fail("Pallas kernels would run in interpret mode")
    info(f"device platform={dev.platform} kind={dev.device_kind} "
         f"count={len(devices)}")
    return devices


def memory_note(dev) -> str:
    stats = dev.memory_stats() or {}
    gib = 2 ** 30
    return (f"peak {stats.get('peak_bytes_in_use', 0) / gib:.2f} GiB of "
            f"{stats.get('bytes_limit', 0) / gib:.2f} GiB")


def serve(engine, requests, max_ticks: int):
    """Submit every request, tick until drained; returns the streams."""
    for r in requests:
        engine.submit(r)
    ticks = 0
    while engine.has_pending_work:
        engine.step()
        ticks += 1
        if ticks > max_ticks:
            fail(f"engine did not drain within {max_ticks} ticks")
    unfinished = [r.uid for r in requests if not r.done]
    if unfinished:
        fail(f"requests {unfinished} did not finish")
    return [list(map(int, r.out_tokens)) for r in requests]


def timed_serve(label, engine, make_requests, max_ticks):
    """Cold pass (compiles included), then a warm pass of the same
    requests; the two must agree token for token."""
    from repro.attention import record_backends

    t0 = time.perf_counter()
    with record_backends() as resolved:
        cold = serve(engine, make_requests(0), max_ticks)
    t1 = time.perf_counter()
    warm = serve(engine, make_requests(len(cold)), max_ticks)
    t2 = time.perf_counter()
    if warm != cold:
        fail(f"{label}: the warm pass differs from the cold pass")
    info(f"{label}: cold pass {t1 - t0:.1f} s (compiles included), "
         f"warm pass {t2 - t1:.1f} s (wall clock); backends "
         f"{ {m: sorted(n) for m, n in sorted(resolved.items())} }")
    return cold, resolved


# ---------------------------------------------------------------------------
# LM phase: codeqwen1.5-7b
# ---------------------------------------------------------------------------
def lm_config(backend: str):
    from repro.configs import get_config, with_overrides

    return with_overrides(
        get_config("codeqwen15_7b"),
        num_layers=LM_LAYERS,
        attention__impl="ssa",
        attention__spike_storage="packed",
        attention__cache_layout="paged",
        attention__backend=backend,
    )


def lm_requests(seed: int, vocab: int):
    from repro.serving import Request

    rng = np.random.default_rng(seed)
    lo, hi = LM_PROMPT_LENS
    lens = rng.integers(lo, hi + 1, LM_REQUESTS)
    lens[0], lens[1] = lo, hi
    prompts = [rng.integers(0, vocab, n).astype(np.int32) for n in lens]

    def make(uid0: int):
        return [Request(uid=uid0 + i, prompt=p, max_new_tokens=LM_NEW_TOKENS,
                        seed=seed + i)
                for i, p in enumerate(prompts)]

    return make, lens


def lm_params(model, seed: int, sharding=None):
    import jax

    key = jax.random.PRNGKey(seed)
    init = jax.jit(model.init, out_shardings=sharding) if sharding else model.init
    t0 = time.perf_counter()
    params = jax.block_until_ready(init(key))
    info(f"lm: params initialised in {time.perf_counter() - t0:.1f} s "
         "(compile included)")
    return params


def lm_engine(model, params, **kw):
    from repro.serving import ServingEngine

    return ServingEngine(model, params, num_slots=LM_SLOTS,
                         max_seq=LM_MAX_SEQ, **kw)


def lm_announce(cfg, lens):
    a = cfg.attention
    info(f"lm: codeqwen1.5-7b d_model={cfg.d_model} heads={a.num_heads}x"
         f"{a.head_dim} kv_heads={a.num_kv_heads} d_ff={cfg.d_ff} "
         f"vocab={cfg.vocab_size} T={a.ssa_time_steps}")
    info(f"lm: depth cut to {cfg.num_layers} of 32 layers: 32 bf16 layers "
         f"are ~16.4 GB of weights, more than one 16 GiB chip; "
         f"{cfg.num_layers} leave room for the {LM_SLOTS} x {LM_MAX_SEQ}-row "
         "packed cache and the decode step's temporaries")
    info(f"lm: {len(lens)} requests, prompts {sorted(int(n) for n in lens)} "
         f"tokens, {LM_NEW_TOKENS} new tokens each, {LM_SLOTS} slots, "
         f"max_seq {LM_MAX_SEQ}, chunked prefill (one page per chunk)")


def phase_lm(devices, seed: int) -> None:
    from repro.models import build_model

    cfg = lm_config("auto")
    model = build_model(cfg)
    make, lens = lm_requests(seed, cfg.vocab_size)
    lm_announce(cfg, lens)
    params = lm_params(model, seed)

    t0 = time.perf_counter()
    engine = lm_engine(model, params)
    info(f"lm: engine built in {time.perf_counter() - t0:.1f} s; kv pool "
         f"{engine.kv_cache_nbytes() / 2**30:.2f} GiB")
    fused, resolved = timed_serve("lm fused", engine, make, LM_MAX_TICKS)
    # chunked prefill is the prefix-extend ("decode") call; one-shot
    # prefill only runs where chunking does not apply
    if resolved.get("decode") != {"ssa-fused-packed"}:
        fail(f"lm: decode resolved to {resolved.get('decode')}, "
             "expected ssa-fused-packed")
    if resolved.get("prefill", {"ssa-fused"}) != {"ssa-fused"}:
        fail(f"lm: prefill resolved to {resolved['prefill']}, "
             "expected ssa-fused")
    del engine
    gc.collect()
    info(f"lm fused: device memory {memory_note(devices[0])}")

    xla_model = build_model(lm_config("xla"))
    engine = lm_engine(xla_model, params)
    xla, resolved = timed_serve("lm xla", engine, make, LM_MAX_TICKS)
    if resolved.get("decode") != {"ssa-xla"}:
        fail(f"lm: xla engine resolved {resolved}")
    del engine
    gc.collect()
    if fused != xla:
        bad = [i for i, (f, x) in enumerate(zip(fused, xla)) if f != x]
        fail(f"lm: fused and xla token streams differ for requests {bad}")
    info(f"lm: fused == xla for all {len(fused)} streams "
         f"({sum(map(len, fused))} tokens); first stream {fused[0][:8]}...")


# ---------------------------------------------------------------------------
# ViT phase: spiking-vit-small
# ---------------------------------------------------------------------------
def vit_config(backend: str):
    from repro.configs import get_config, with_overrides

    return with_overrides(
        get_config("spiking_vit_small"),
        attention__cache_layout="paged",
        attention__backend=backend,
    )


def vit_classes(backend: str, params, seed: int):
    from repro.models import build_model
    from repro.serving import Request, ServingEngine

    model = build_model(vit_config(backend))
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, model.num_events, model.num_patches)
               .astype(np.int32) for _ in range(VIT_REQUESTS)]

    def make(uid0: int):
        return [Request(uid=uid0 + i, prompt=p, max_new_tokens=1,
                        seed=seed + i) for i, p in enumerate(prompts)]

    engine = ServingEngine(model, params, num_slots=VIT_SLOTS,
                           max_seq=model.num_patches, page_size=16)
    out, resolved = timed_serve(f"vit {backend}", engine, make,
                                max_ticks=10 * VIT_REQUESTS)
    if any(len(s) != 1 for s in out):
        fail(f"vit {backend}: expected one class per request")
    return [s[0] for s in out], resolved


def phase_vit(seed: int) -> None:
    import jax
    from repro.models import build_model

    cfg = vit_config("auto")
    a = cfg.attention
    info(f"vit: spiking-vit-small layers={cfg.num_layers} d_model="
         f"{cfg.d_model} heads={a.num_heads}x{a.head_dim} T="
         f"{a.ssa_time_steps}; {VIT_REQUESTS} event-stream requests")
    params = build_model(cfg).init(jax.random.PRNGKey(seed))
    fused, resolved = vit_classes("auto", params, seed)
    if resolved.get("prefill") != {"ssa-fused"}:
        fail(f"vit: prefill resolved to {resolved.get('prefill')}, "
             "expected ssa-fused")
    xla, _ = vit_classes("xla", params, seed)
    if fused != xla:
        bad = [i for i, (f, x) in enumerate(zip(fused, xla)) if f != x]
        fail(f"vit: fused and xla classes differ for requests {bad}")
    info(f"vit: fused == xla for all {len(fused)} classes: {fused}")


# ---------------------------------------------------------------------------
# four chips: head-sharded LM engine vs the one-chip engine
# ---------------------------------------------------------------------------
def shard_bytes(engine) -> dict:
    """Bytes of KV cache resident on each device, read from the shards."""
    per: dict = {}
    import jax

    for leaf in jax.tree.leaves(engine.cache):
        for shard in leaf.addressable_shards:
            per[shard.device.id] = per.get(shard.device.id, 0) + shard.data.nbytes
    return dict(sorted(per.items()))


def phase_four_chips(devices, seed: int) -> None:
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.launch.mesh import make_local_mesh
    from repro.models import build_model

    if len(devices) < 4:
        fail(f"--four-chips needs 4 devices, found {len(devices)}")
    cfg = lm_config("auto")
    model = build_model(cfg)
    make, lens = lm_requests(seed, cfg.vocab_size)
    lm_announce(cfg, lens)

    params = lm_params(model, seed)
    engine = lm_engine(model, params)
    one, _ = timed_serve("lm one chip", engine, make, LM_MAX_TICKS)
    del engine, params
    gc.collect()

    # params drawn replicated in place: a copy next to the one-chip
    # params would not fit device 0
    mesh = make_local_mesh(model=4)
    params = lm_params(model, seed, NamedSharding(mesh, PartitionSpec()))
    engine = lm_engine(model, params, mesh_shards=4)
    four, resolved = timed_serve("lm 4 shards", engine, make, LM_MAX_TICKS)
    if resolved.get("decode") != {"ssa-fused-packed"}:
        fail(f"lm 4 shards: decode resolved to {resolved.get('decode')}")
    per = shard_bytes(engine)
    info("lm 4 shards: kv bytes per device "
         + ", ".join(f"{d}: {b / 2**20:.1f} MiB" for d, b in per.items())
         + f"; logical total {engine.kv_cache_nbytes() / 2**20:.1f} MiB")
    if one != four:
        bad = [i for i, (a, b) in enumerate(zip(one, four)) if a != b]
        fail(f"4-shard streams differ from one-chip streams for {bad}")
    info(f"lm: 4-shard == one-chip for all {len(one)} streams")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-way head-sharded LM phase against "
                         "the one-chip engine (needs 4 devices)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed for weights, prompts and request seeds")
    args = ap.parse_args()

    devices = check_device()
    from repro.launch.compile_cache import enable_compile_cache

    info(f"compile cache: {enable_compile_cache()}")
    t0 = time.perf_counter()
    if args.four_chips:
        phase_four_chips(devices, args.seed)
    else:
        phase_lm(devices, args.seed)
        phase_vit(args.seed)
    dev = devices[0]
    info(f"all phases passed in {time.perf_counter() - t0:.1f} s; device "
         f"memory {memory_note(dev)}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}))


if __name__ == "__main__":
    main()
