"""Arch registry: ``--arch <id>`` -> ModelConfig (full) / smoke (reduced)."""
from __future__ import annotations

import ast
import importlib
import os

from .base import SHAPES, ModelConfig, ShapeConfig, with_overrides

ARCH_IDS = [
    "gemma2_9b",
    "codeqwen15_7b",
    "phi4_mini_3_8b",
    "yi_34b",
    "qwen2_vl_2b",
    "xlstm_125m",
    "deepseek_moe_16b",
    "mixtral_8x7b",
    "zamba2_1_2b",
    "whisper_small",
    "spiking_vit_small",   # the paper's own architecture
    "mellum2_12b_a2_5b",
]

_ALIASES = {
    "gemma2-9b": "gemma2_9b",
    "codeqwen1.5-7b": "codeqwen15_7b",
    "phi4-mini-3.8b": "phi4_mini_3_8b",
    "yi-34b": "yi_34b",
    "qwen2-vl-2b": "qwen2_vl_2b",
    "xlstm-125m": "xlstm_125m",
    "deepseek-moe-16b": "deepseek_moe_16b",
    "mixtral-8x7b": "mixtral_8x7b",
    "zamba2-1.2b": "zamba2_1_2b",
    "whisper-small": "whisper_small",
    "spiking-vit-small": "spiking_vit_small",
    "mellum2-12b-a2.5b": "mellum2_12b_a2_5b",
}


def canonical(arch: str) -> str:
    arch = _ALIASES.get(arch, arch).replace("-", "_").replace(".", "_")
    if arch not in ARCH_IDS:
        raise KeyError(f"unknown arch {arch!r}; available: {ARCH_IDS}")
    return arch


def get_config(arch: str) -> ModelConfig:
    mod = importlib.import_module(f"repro.configs.{canonical(arch)}")
    return mod.CONFIG


def _env_smoke_overrides() -> dict:
    """Parse ``REPRO_SMOKE_OVERRIDES`` ("attention__impl=ssa,..." with
    ``with_overrides`` path syntax) — the hook CI lanes use to re-run whole
    test suites under a different attention/cache configuration."""
    spec = os.environ.get("REPRO_SMOKE_OVERRIDES", "").strip()
    out: dict = {}
    if not spec:
        return out
    for item in spec.replace(";", ",").split(","):
        item = item.strip()
        if not item:
            continue
        key, _, val = item.partition("=")
        try:
            out[key.strip()] = ast.literal_eval(val.strip())
        except (ValueError, SyntaxError):
            out[key.strip()] = val.strip()
    return out


def get_smoke_config(arch: str) -> ModelConfig:
    mod = importlib.import_module(f"repro.configs.{canonical(arch)}")
    cfg = mod.smoke_config()
    env = _env_smoke_overrides()
    return with_overrides(cfg, **env) if env else cfg


def get_shape(name: str) -> ShapeConfig:
    return SHAPES[name]


def cells(include_skipped: bool = False):
    """All assigned (arch x shape) dry-run cells with skip annotations."""
    from .applicability import cell_status

    out = []
    for arch in ARCH_IDS:
        if arch == "spiking_vit_small":
            continue  # paper arch: own benchmark path, not an assigned cell
        for shape in SHAPES:
            status, why = cell_status(arch, shape)
            if status == "run" or include_skipped:
                out.append((arch, shape, status, why))
    return out
