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


def mrope_sin_cos(positions, head_dim: int, sections, *,
                  theta: float = 500000.0):
    """(sin, cos) tables from SEVERAL position axes by sections (M-RoPE:
    a token of an image or a video has a time, a height and a width; a
    text token's axes are equal and this is ``rope_sin_cos``).
    ``positions`` [axes, ...] integer; ``sections``: how many of the
    ``head_dim // 2`` frequency pairs take their angle from each axis, in
    order (Keye-VL's [16, 24, 24]: pairs 0-15 from axis 0, 16-39 from
    axis 1, 40-63 from axis 2). Returns fp32 [..., head_dim // 2], what
    ``apply_rope`` takes."""
    if sum(sections) != head_dim // 2 or len(sections) != positions.shape[0]:
        raise ValueError(
            f"sections {list(sections)} over {positions.shape[0]} axes do "
            f"not add up to head_dim // 2 = {head_dim // 2}")
    with jax.named_scope(scopes.ATTN_QKV):
        inv_freq = rope_frequencies(head_dim, theta=theta)
        # which axis each frequency pair reads
        axis = jnp.repeat(jnp.arange(len(sections)), jnp.asarray(sections),
                          total_repeat_length=head_dim // 2)
        by_axis = jnp.moveaxis(positions.astype(jnp.float32), 0, -1)
        angles = by_axis[..., axis] * inv_freq
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
