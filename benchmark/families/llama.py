"""Dense GQA decoders served by ``ray_tpu.models.llama`` (Llama, Mistral):
the adapter from the published Hugging Face keys to the program's config
class, the plain reference of the block, and its operation and byte
counts (``benchmark/families/__init__.py`` says what a family is).

The reference follows the published description (Hugging Face
``MistralForCausalLM``): RMSNorm in float32, rotary embedding in the
half-rotation layout with ``rope_theta``, grouped-query causal attention
(no sliding window in v0.3), SwiGLU feed-forward, untied output head. It
reads the program's parameter layout (a dict with the blocks stacked on a
leading layer axis), which is data, not code. Weights are converted to
float32 one layer at a time, so one layer's float32 copy is live at once.
On a TPU a float32 matrix multiplication runs in lower precision unless
told otherwise: ``logits`` runs under
``jax.default_matmul_precision("highest")``.

The counts take one dense block with one ``intermediate_size``: every
weight takes part in every token.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


# -- the adapter: the one part that touches the program -----------------

def model_config(config: dict):
    from ray_tpu.models import llama

    return llama.LlamaConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], head_dim=config["head_dim"],
        rope_theta=float(config["rope_theta"]),
        rms_eps=float(config["rms_norm_eps"]),
        dtype={"bfloat16": "bfloat16", "float32": "float32"}[
            config["torch_dtype"]],
        remat=config["system"].get("remat", "none"),
        tie_embeddings=config["tie_word_embeddings"])


def init_params(model_cfg, key):
    from ray_tpu.models import llama

    return llama.init_params(model_cfg, key)


# -- the plain reference -------------------------------------------------

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


def logits(config: dict, params: dict, tokens) -> jax.Array:
    """Float32 logits [b, s, vocab] of ``tokens`` [b, s]."""
    kw = dict(heads=config["num_attention_heads"],
              kv_heads=config["num_key_value_heads"],
              head_dim=config["head_dim"], theta=float(config["rope_theta"]),
              eps=float(config["rms_norm_eps"]))
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embedding"], tokens)
        for i in range(config["num_hidden_layers"]):
            x = _layer(x, jax.tree.map(lambda a: a[i], params["blocks"]),
                       **kw)
        head = (params["embedding"].T if config["tie_word_embeddings"]
                else params["lm_head"])
        return _head(x, params["final_norm"], head, eps=kw["eps"])


# -- the counts ----------------------------------------------------------

def matmul_params(m: dict) -> int:
    """Parameters that take part in a matrix multiplication per token:
    the blocks and the output head, not the embedding lookup."""
    d, ff = m["hidden_size"], m["intermediate_size"]
    q = m["num_attention_heads"] * m["head_dim"]
    kv = m["num_key_value_heads"] * m["head_dim"]
    block = d * q + 2 * d * kv + q * d + 3 * d * ff
    return m["num_hidden_layers"] * block + d * m["vocab_size"]


def total_params(m: dict) -> int:
    d = m["hidden_size"]
    norms = (2 * m["num_hidden_layers"] + 1) * d
    return matmul_params(m) + d * m["vocab_size"] + norms


def attention_flops_fwd(m: dict, batch: int, seq: int) -> float:
    """Causal attention, forward, over ``batch`` sequences of ``seq``: QK^T
    and PV, 2 operations a multiply-add, half the square being masked."""
    q = m["num_attention_heads"] * m["head_dim"]
    return m["num_hidden_layers"] * batch * 0.5 * (4.0 * seq * seq * q)


def train_flops_per_token(m: dict, seq: int) -> float:
    """Forward plus backward (3 x forward) of a causal LM at sequence
    length ``seq``; recomputation is not counted."""
    return 6.0 * matmul_params(m) + 3.0 * attention_flops_fwd(m, 1, seq) / seq


def flash_train_cost(m: dict, batch: int, seq: int) -> dict:
    """What the flash kernels of one train step (forward, dq, dk/dv) must
    do: operations (backward = 2.5 x forward: it recomputes the scores) and
    HBM bytes (q, k, v, o read or written once forward; q, k, v, o, do
    read and dq, dk, dv written backward), bf16."""
    layers = m["num_hidden_layers"]
    q = m["num_attention_heads"] * m["head_dim"]
    kv = m["num_key_value_heads"] * m["head_dim"]
    fwd = attention_flops_fwd(m, batch, seq)
    tok = batch * seq * layers * 2          # bf16 bytes per unit width
    fwd_bytes = tok * (2 * q + 2 * kv)
    bwd_bytes = tok * (4 * q + 4 * kv)
    return {"flops": 3.5 * fwd, "bytes": fwd_bytes + bwd_bytes}


def kv_bytes_per_token_layer(m: dict) -> int:
    """Keys and values of one token in one layer, bf16."""
    return 2 * 2 * m["num_key_value_heads"] * m["head_dim"]


def attention_kv_bytes(m: dict, counters: dict) -> float:
    """Bytes of keys and values one decode step must read: every live
    token's (the counter ``live_kv_tokens_mean``) in every layer
    (``paged_attn_roofline``)."""
    return (kv_bytes_per_token_layer(m) * m["num_hidden_layers"]
            * counters.get("live_kv_tokens_mean", 0.0))


def decode_step_bytes(m: dict, counters: dict) -> float:
    """HBM bytes one decode step must move: every weight once (bf16; the
    embedding rows read are negligible) and the live keys and values
    (the counter ``live_kv_tokens_mean``) once."""
    return 2.0 * matmul_params(m) + attention_kv_bytes(m, counters)
