"""Numerics of the reference: where values are stored, and how products run.

A mode is (storage, products):

* ``f32``: float32 storage, products at ``highest`` precision;
* ``bf16``: bfloat16 storage wherever the layer equations hand a value on
  (weights, projections, norms, residual sums), exact products of the
  stored values accumulated in float32: the numerics a bfloat16
  configuration states;
* ``int8``: bfloat16 storage, products of symmetric int8 quantisations
  (weights per output channel, activations per row), accumulated exactly:
  the W8A8 path a v5e's int8 units invite, the control one precision below
  a bfloat16 configuration.

The control of a configuration is the mode one precision below it
(``control_of``).  Two planted faults confine the control's int8 to part
of the stack (``PARTIAL``): ``int8_head``, the output head alone, and
``int8_deep``, every layer from ``DEEP_FROM`` on and the head.  They show
what a lower precision in the layers the cached-spike check does not
compare reads.
"""
from __future__ import annotations

import jax.numpy as jnp

MODES = {"f32": (jnp.float32, "exact"),
         "bf16": (jnp.bfloat16, "exact"),
         "int8": (jnp.bfloat16, "int8")}
# first layer that ``int8_deep`` runs in int8
DEEP_FROM = 2
PARTIAL = ("int8_head", "int8_deep")


def mode_of(config: dict) -> str:
    """The numerics a configuration states: its dtype."""
    return {"bfloat16": "bf16", "float32": "f32"}[config["torch_dtype"]]


def control_of(config: dict) -> str:
    """The control: the nearest precision below the configuration's."""
    return {"bf16": "int8", "f32": "bf16"}[mode_of(config)]


def layer_mode(mode: str, layer: int) -> str:
    """The numerics of one layer under ``mode`` (a partial fault runs the
    layers it leaves alone in bfloat16)."""
    if mode == "int8_head":
        return "bf16"
    if mode == "int8_deep":
        return "int8" if layer >= DEEP_FROM else "bf16"
    return mode


def head_mode(mode: str) -> str:
    """The numerics of the output head under ``mode``."""
    return "int8" if mode in PARTIAL else mode


def store(x, mode: str):
    """Round ``x`` to the mode's storage type (kept as float32 values)."""
    return x.astype(MODES[mode][0]).astype(jnp.float32)


def _quant(x, axis):
    """Symmetric int8 quantise-dequantise along ``axis`` (per slice scale)."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def matmul(x, w, mode: str):
    """x (..., K) @ w (K, N) of stored values; the result is stored."""
    x = store(x, mode)
    w = store(w, mode)
    if MODES[mode][1] == "int8":
        x, w = _quant(x, -1), _quant(w, 0)
    # products of bfloat16 values are exact at the default precision
    prec = "default" if mode == "bf16" else "highest"
    return store(jnp.matmul(x, w, precision=prec), mode)
