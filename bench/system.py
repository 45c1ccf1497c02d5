"""The system under test, built from a configuration file through the public API.

A configuration file (``bench/configs/<name>.json``) holds the model's
sizes under the published keys, the attention and engine settings, and a
``program`` entry naming the repository's base configuration and the
overrides that make it.  The build checks that the program's
configuration carries every size the file states, so the file is the
configuration as it is run.  What differs between model families lives
in ``bench/families/<family>.py``.
"""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np

from . import families, traffic

ROOT = Path(__file__).resolve().parents[1]


def weights_key(seed: int):
    """Raw threefry key for the weights, from any whole-number seed."""
    import jax.numpy as jnp

    words = np.random.SeedSequence([int(seed), 0x57454947]).generate_state(2)
    return jnp.asarray(words, dtype=jnp.uint32)


def _program_config(cfg_file: dict):
    from repro.configs import get_config, with_overrides

    prog = cfg_file["program"]
    return with_overrides(get_config(prog["arch"]), **prog["overrides"])


def _stated_sizes(cfg_file: dict, cfg, model, family) -> dict:
    """{key: (value in the file, value in the program)} for every size."""
    a = cfg.attention
    got = {
        "hidden_size": cfg.d_model,
        "intermediate_size": cfg.d_ff,
        "num_attention_heads": a.num_heads,
        "head_dim": a.head_dim,
        "num_hidden_layers": cfg.num_layers,
        **family.stated_sizes(cfg_file, cfg, model),
    }
    att = {"impl": a.impl, "time_steps": a.ssa_time_steps,
           "spike_storage": a.spike_storage, "backend": a.backend,
           "cache_layout": a.cache_layout, "causal": a.causal}
    out = {k: (cfg_file[k], v) for k, v in got.items()}
    out.update({f"attention.{k}": (cfg_file["attention"][k], v)
                for k, v in att.items()})
    return out


class System:
    """Model, weights and serving engines of one configuration."""

    def __init__(self, cfg_file: dict, seed: int):
        import jax

        sys.path.insert(0, str(ROOT / "src"))
        from repro.models import build_model

        self.file = cfg_file
        self.family = families.load(cfg_file["family"])
        self.cfg = _program_config(cfg_file)
        self.model = build_model(self.cfg)
        wrong = {k: v for k, v in
                 _stated_sizes(cfg_file, self.cfg, self.model, self.family).items()
                 if v[0] != v[1]}
        if wrong:
            raise ValueError(f"configuration file and program disagree: {wrong}")
        self.engine_kw = dict(cfg_file["engine"])
        self.key = weights_key(seed)
        # one jitted call on the device, in the dtype the model serves
        self.params = jax.block_until_ready(self.family.init_params(self.model, self.key))
        widest = max(jax.tree.leaves(self.params), key=lambda x: x.size)
        if str(widest.dtype) != cfg_file["torch_dtype"]:
            raise ValueError(f"weights are {widest.dtype}, the file states "
                             f"{cfg_file['torch_dtype']}")

    @property
    def vocab(self) -> int:
        return self.family.vocab(self.file)

    @property
    def max_total(self) -> int:
        """Largest prompt + output served without a cut by ``max_seq``."""
        return self.family.max_total(self.file)

    def engine(self, tracer=None):
        from repro.serving import ServingEngine

        return ServingEngine(self.model, self.params, tracer=tracer, **self.engine_kw)

    @staticmethod
    def request(uid: int, planned: traffic.PlannedRequest):
        from repro.serving import Request

        return Request(uid=uid, prompt=planned.prompt,
                       max_new_tokens=planned.max_new_tokens, seed=planned.seed)

    def kv_snapshot(self, engine, records, layers: int) -> list:
        return self.family.kv_snapshot(engine, records, layers)

    def warmup_prompts(self, plan: traffic.Plan) -> list[tuple[int, int]]:
        return self.family.warmup_prompts(self.file, plan)

    def free(self):
        """Drop the program's weights (the reference runs after)."""
        self.params = None
        self.model = None
