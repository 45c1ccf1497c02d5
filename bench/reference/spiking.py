"""Plain float32 spiking pieces: counter RNG, LIF rate coding, SSA (eq. 4-6).

Written from the layer equations and the repository's documented RNG
contract (version 2, "request-addressed"): every Bernoulli draw is a pure
function of (request seed, layer, head, time step, absolute position,
channel).  Nothing here imports the program; the constants below are the
contract's.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# splitmix32 finaliser multipliers
_M1 = np.uint32(0x7FEB352D)
_M2 = np.uint32(0x846CA68B)
# seed-derivation salts: row, layer, head, time step
_LAYER_SALT = np.uint32(0x632BE5AB)
_HEAD_SALT = np.uint32(0x85EBCA6B)
_STEP_SALT = np.uint32(0xC2B2AE35)
# eq. 5 (score) and eq. 6 (output) Bernoulli banks
SALT_S = np.uint32(0x9E3779B9)
SALT_A = np.uint32(0x85EBCA6B)
POS_STRIDE_S = np.uint32(0x9E3779B1)
POS_STRIDE_A = np.uint32(0x85EBCA77)
# LIF neuron (eq. 4): leak, firing threshold; soft reset
LIF_BETA = 0.9
LIF_THRESHOLD = 1.0


def mix32(x):
    x = jnp.asarray(x).astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * _M1
    x = x ^ (x >> 15)
    x = x * _M2
    return x ^ (x >> 16)


def uniform(seed, counter):
    """Uniform [0, 1) with 24 bits, from a seeded counter hash."""
    h = mix32(counter.astype(jnp.uint32) + mix32(seed))
    return (h >> np.uint32(8)).astype(jnp.int32).astype(jnp.float32) * np.float32(
        1.0 / (1 << 24))


def layer_seed(request_seed, layer):
    """Per-request seed folded with the flat layer index."""
    li = jnp.asarray(layer).astype(jnp.uint32)
    return mix32(jnp.asarray(request_seed, jnp.uint32) ^ mix32(li * _LAYER_SALT + 1))


def step_seeds(seed_l, t_steps: int, heads: int):
    """(T, H) stream seeds of one request in one layer."""
    h = jnp.arange(heads, dtype=jnp.uint32)
    t = jnp.arange(t_steps, dtype=jnp.uint32)
    s = mix32(seed_l + h * _HEAD_SALT)                     # (H,)
    return mix32(s[None, :] + t[:, None] * _STEP_SALT)     # (T, H)


def spike_encode(x, t_steps: int):
    """(S, H, hd) real activations -> (T, S, H, hd) 0/1 spikes.

    Each token's head vector is scaled to unit RMS, driven through a
    softplus and fed for T steps to a LIF neuron (eq. 4).
    """
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6)
    drive = jax.nn.softplus(x)
    v = jnp.zeros_like(drive)
    out = []
    for _ in range(t_steps):
        v = LIF_BETA * v + drive
        s = (v - LIF_THRESHOLD >= 0).astype(jnp.float32)
        v = v - LIF_THRESHOLD * s
        out.append(s)
    return jnp.stack(out)


def ssa_head(qs, ks, vs, seed, q_pos, kv_pos, *, causal: bool):
    """Stochastic spiking attention of one head at one time step.

    qs (Sq, hd), ks/vs (Skv, hd) 0/1; seed uint32; positions int32 absolute
    token positions.  eq. 5: a score spike fires where ``u * hd < q.k``;
    eq. 6: an output spike fires where ``u * visible < sum_k s v``, with
    ``visible`` the keys the query may see.  Returns (Sq, hd) 0/1.  The
    spike counts are sums of 0/1 products, exact at any matmul precision.
    """
    hd = qs.shape[-1]
    counts = jnp.dot(qs, ks.T, precision="default", preferred_element_type=jnp.float32)
    valid = (kv_pos[None, :] >= 0) & (q_pos[:, None] >= 0)
    if causal:
        valid &= kv_pos[None, :] <= q_pos[:, None]
    qp = jnp.maximum(q_pos, 0).astype(jnp.uint32)
    kp = jnp.maximum(kv_pos, 0).astype(jnp.uint32)
    u_s = uniform(seed ^ SALT_S, qp[:, None] * POS_STRIDE_S + kp[None, :])
    s = (valid & (u_s * np.float32(hd) < counts)).astype(jnp.float32)
    counts_a = jnp.dot(s, vs, precision="default", preferred_element_type=jnp.float32)
    visible = jnp.maximum(valid.sum(-1), 1).astype(jnp.float32)[:, None]
    col = jnp.arange(hd, dtype=jnp.uint32)
    u_a = uniform(seed ^ SALT_A, qp[:, None] * POS_STRIDE_A + col[None, :])
    return (u_a * visible < counts_a).astype(jnp.float32)


def ssa_attention(q, k, v, seed_l, positions, *, t_steps: int, causal: bool):
    """Multi-head SSA with rate decoding.

    q (S, H, hd), k/v (S, H_kv, hd) real; returns (S, H, hd) spike rates
    (mean over the T time steps).  Query head ``h`` reads kv head
    ``h // (H / H_kv)``.  Heads run side by side, time steps one after
    another.
    """
    heads, kv_heads = q.shape[1], k.shape[1]
    kv_of = jnp.arange(heads) // (heads // kv_heads)
    qs = spike_encode(q, t_steps).transpose(0, 2, 1, 3)          # (T, H, S, hd)
    ks = spike_encode(k, t_steps).transpose(0, 2, 1, 3)[:, kv_of]
    vs = spike_encode(v, t_steps).transpose(0, 2, 1, 3)[:, kv_of]
    seeds = step_seeds(seed_l, t_steps, heads)                   # (T, H)
    per_head = jax.vmap(
        lambda a, b, c, sd: ssa_head(a, b, c, sd, positions, positions, causal=causal))
    out = jax.lax.map(lambda x: per_head(*x), (qs, ks, vs, seeds))  # (T, H, S, hd)
    return out.mean(axis=0).transpose(1, 0, 2)
