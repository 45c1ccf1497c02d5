"""Shared kernel utilities: in-kernel counter RNG and tiling helpers.

The hardware design uses per-encoder LFSR PRNGs (Sec. III-D).  On TPU we want
an RNG that (i) runs inside a Pallas kernel body, (ii) is *stateless* — the
uniform for logical position (b, i, j) must not depend on how the kernel is
tiled, so forward/backward recomputation and resharding give identical bits —
and (iii) vectorises.  A counter-based hash (splitmix32 finaliser) satisfies
all three; it is the TPU-native stand-in for the paper's LFSR bank, and the
same jnp expression runs unchanged inside kernels, in the jnp reference
oracles, and in interpret mode on CPU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "mix32",
    "uniform_from_counter",
    "unpack_words_to_lanes",
    "pad_to_multiple",
    "cdiv",
]

# numpy scalars stay jaxpr literals (jnp constants would be captured consts,
# which pallas_call rejects inside kernel bodies).
_M1 = np.uint32(0x7FEB352D)
_M2 = np.uint32(0x846CA68B)


def mix32(x: jnp.ndarray) -> jnp.ndarray:
    """splitmix32-style avalanche finaliser on uint32 (wraps mod 2^32)."""
    x = x.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * _M1
    x = x ^ (x >> 15)
    x = x * _M2
    x = x ^ (x >> 16)
    return x


def uniform_from_counter(seed: jnp.ndarray, counter: jnp.ndarray) -> jnp.ndarray:
    """Uniform[0,1) float32 per counter lane, seeded stream.

    ``seed`` uint32 scalar/tensor, ``counter`` uint32 tensor of logical
    positions.  24 mantissa-exact bits — the same resolution class as the
    paper's 16-bit LFSR comparators, with margin.
    """
    h = mix32(counter.astype(jnp.uint32) + mix32(seed))
    # Mosaic has no uint32 -> f32 cast; the value is < 2^24, so the detour
    # through int32 is exact.
    r = (h >> np.uint32(8)).astype(jnp.int32)
    return r.astype(jnp.float32) * np.float32(1.0 / (1 << 24))


def unpack_words_to_lanes(words):
    """(rows, W) uint32 bit-planes -> (rows, W * 32) f32 0/1 lanes.

    Little-endian bit order, matching ``repro.bitpack.pack_spikes``.  Pure
    jnp on uint32 shifts, so it runs identically inside Pallas kernel bodies
    (VMEM tiles) and in jnp reference paths — the single place the packed
    word layout is decoded on the kernel side.
    """
    rows, w = words.shape
    shifts = jax.lax.broadcasted_iota(jnp.uint32, (1, 1, 32), 2)
    bits = (words[:, :, None] >> shifts) & np.uint32(1)
    return bits.reshape(rows, w * 32).astype(jnp.int32).astype(jnp.float32)


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def pad_to_multiple(x, axis: int, multiple: int, value=0.0):
    """Pad ``axis`` of ``x`` up to a multiple; returns (padded, original_size)."""
    size = x.shape[axis]
    target = cdiv(size, multiple) * multiple
    if target == size:
        return x, size
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, target - size)
    return jnp.pad(x, pad, constant_values=value), size
