"""Plain reference of the Mistral / Llama decoder: the forward pass and the
next-token loss in straightforward ``jax.numpy`` and float32, with no
kernels, no cache, no batching tricks and nothing imported from the program.

Follows the published description (Hugging Face ``MistralForCausalLM``):
RMSNorm in float32, rotary embedding in the half-rotation layout with
``rope_theta``, grouped-query causal attention (no sliding window in v0.3),
SwiGLU feed-forward, untied output head. It reads the program's parameter
layout (a dict with the blocks stacked on a leading layer axis), which is
data, not code. Weights are converted to float32 one layer at a time, so
one layer's float32 copy is live at once. On a TPU a float32 matrix
multiplication runs in lower precision unless told otherwise: every entry
point here runs under ``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rope(x, positions, theta):
    """x [b, s, h, hd]; rotate pairs (i, i + hd/2)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions[..., None].astype(jnp.float32) * inv        # [b, s, hd/2]
    sin, cos = jnp.sin(ang)[:, :, None, :], jnp.cos(ang)[:, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "head_dim",
                                             "theta", "eps"))
def _layer(x, p, *, heads, kv_heads, head_dim, theta, eps):
    """One decoder block on x [b, s, d] float32; p holds this layer's
    weights in their stored dtype."""
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    b, s, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    h = _rms_norm(x, p["attn_norm"], eps)
    q = _rope((h @ p["wq"]).reshape(b, s, heads, head_dim), pos, theta)
    k = _rope((h @ p["wk"]).reshape(b, s, kv_heads, head_dim), pos, theta)
    v = (h @ p["wv"]).reshape(b, s, kv_heads, head_dim)
    rep = heads // kv_heads
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * head_dim ** -0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    attn = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    x = x + attn.reshape(b, s, heads * head_dim) @ p["wo"]
    h = _rms_norm(x, p["mlp_norm"], eps)
    return x + (jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) @ p["w_down"]


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, lm_head, *, eps):
    x = _rms_norm(x, final_norm.astype(jnp.float32), eps)
    return x @ lm_head.astype(jnp.float32)


@jax.jit
def _embed(embedding, tokens):
    return embedding[tokens].astype(jnp.float32)


def logits(model: dict, params: dict, tokens) -> jax.Array:
    """Float32 logits [b, s, vocab] of ``tokens`` [b, s]. ``model`` holds
    the published keys of a configuration file."""
    kw = dict(heads=model["num_attention_heads"],
              kv_heads=model["num_key_value_heads"],
              head_dim=model["head_dim"], theta=float(model["rope_theta"]),
              eps=float(model["rms_norm_eps"]))
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embedding"], tokens)
        for i in range(model["num_hidden_layers"]):
            x = _layer(x, jax.tree.map(lambda a: a[i], params["blocks"]),
                       **kw)
        head = (params["embedding"].T if model["tie_word_embeddings"]
                else params["lm_head"])
        return _head(x, params["final_norm"], head, eps=kw["eps"])


@jax.jit
def _nll_sum(lg, targets):
    lse = jax.nn.logsumexp(lg, axis=-1)
    tl = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - tl)


def loss(model: dict, params: dict, batch, rows_per_call: int = 1) -> float:
    """Mean next-token cross entropy of ``batch`` [B, S+1], a few rows at
    a time (float32 logits of a whole batch would not fit beside a train
    state)."""
    total, count = 0.0, 0
    for i in range(0, batch.shape[0], rows_per_call):
        rows = batch[i:i + rows_per_call]
        lg = logits(model, params, rows[:, :-1])
        total += float(_nll_sum(lg, rows[:, 1:]))
        count += rows.shape[0] * (rows.shape[1] - 1)
    return total / count


def token_gap(model: dict, params: dict, prompt, tokens) -> tuple:
    """Teacher-force a served answer through the reference: (the largest
    amount by which the reference's logit of a token the system chose
    falls short of the reference's best logit at that position, how many
    tokens are not the reference's own greedy choice). (0.0, 0) when
    every token is."""
    seq = jnp.concatenate([jnp.asarray(prompt, jnp.int32),
                           jnp.asarray(tokens[:-1], jnp.int32)])[None]
    lg = logits(model, params, seq)[0, len(prompt) - 1:]
    chosen = jnp.take_along_axis(
        lg, jnp.asarray(tokens, jnp.int32)[:, None], axis=1)[:, 0]
    short = lg.max(axis=1) - chosen
    return float(jnp.max(short)), int(jnp.sum(short > 0))
