"""Normalization ops (TPU-first: fp32 accumulation inside bf16 models)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops import scopes


def rms_norm(x, weight, *, eps: float = 1e-5):
    """RMSNorm with float32 statistics regardless of input dtype.

    The variance reduction runs in fp32 (VPU) and the result is cast back, so
    bf16 activations don't lose precision in the norm — the standard TPU
    recipe; XLA fuses the whole thing into one elementwise kernel.
    """
    dtype = x.dtype
    with jax.named_scope(scopes.NORM):
        xf = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        normed = xf * lax.rsqrt(var + eps)
        return (normed * weight.astype(jnp.float32)).astype(dtype)


def layer_norm(x, weight, bias=None, *, eps: float = 1e-5):
    """LayerNorm, fp32 statistics, optional bias."""
    dtype = x.dtype
    with jax.named_scope(scopes.NORM):
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
        normed = (xf - mean) * lax.rsqrt(var + eps)
        out = normed * weight.astype(jnp.float32)
        if bias is not None:
            out = out + bias.astype(jnp.float32)
        return out.astype(dtype)
