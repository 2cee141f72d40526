"""Rotary position embeddings (RoPE), Llama-3 style with NTK scaling hooks."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu.ops import scopes


def rope_frequencies(head_dim: int, *, theta: float = 500000.0,
                     dtype=jnp.float32):
    """Inverse frequencies for the rotary embedding, [head_dim // 2]."""
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return (1.0 / (theta ** exponent)).astype(dtype)


def rope_sin_cos(positions, head_dim: int, *, theta: float = 500000.0):
    """(sin, cos) tables for integer positions [...]. Returned in fp32;
    callers cast after rotation for bf16 accuracy."""
    with jax.named_scope(scopes.ATTN_QKV):
        inv_freq = rope_frequencies(head_dim, theta=theta)
        angles = positions[..., None].astype(jnp.float32) * inv_freq
        return jnp.sin(angles), jnp.cos(angles)               # [..., hd/2]


def apply_rope(x, sin, cos):
    """Rotate q or k: x is [..., seq, heads, head_dim]; sin/cos are
    [..., seq, head_dim//2] (broadcast over the heads axis).

    Uses the split-half convention (first/second half pairs) which lowers to
    two multiplies + adds on the VPU — no gather, XLA-friendly.
    """
    half = x.shape[-1] // 2
    with jax.named_scope(scopes.ATTN_QKV):
        x1, x2 = x[..., :half], x[..., half:]
        sin = sin[..., None, :]  # broadcast over heads
        cos = cos[..., None, :]
        xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
        out1 = xf1 * cos - xf2 * sin
        out2 = xf2 * cos + xf1 * sin
        return jnp.concatenate([out1, out2], axis=-1).astype(x.dtype)
