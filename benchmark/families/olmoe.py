"""Sparse-expert decoders served by ``ray_tpu.models.olmoe`` (OLMoE-1B-7B):
the adapter from the published Hugging Face keys to the program's config
class, the plain reference of the block, and its operation and byte counts
(``benchmark/families/__init__.py`` says what a family is).

The reference follows the published description (Hugging Face
``OlmoeForCausalLM``; arXiv:2409.02060): RMSNorm in float32; **RMSNorm of
q and of k over the whole projection, all heads together, before the split
into heads** (``config.json`` has no key for it; it is in the model code
and the paper); rotary embedding in the half-rotation layout; causal
multi-head attention; a routed feed-forward: ``p = softmax(float32(h) @
router)`` over all experts, the ``num_experts_per_tok`` largest, **their
probabilities as they are unless ``norm_topk_prob``**, ``y = sum_k p_k *
(silu(h @ gate_k) * (h @ up_k)) @ down_k``, every token served by all of
its experts; final RMSNorm; untied head. It reads the program's parameter
layout (a dict with the blocks stacked on a leading layer axis; the
experts on the axis behind it), which is data, not code, and imports
nothing from the program.

One departure in form, none in value: the sum over a token's chosen
experts is taken as a loop over ALL experts, each applied to every token
and kept where it is among the token's chosen (``where``), not as a gather
of each token's eight experts: at the published widths one token's eight
experts are 100 MB, a 631-token check would gather 64 GB a layer, and the
check runs on the chip beside the engine. Weights are converted to float32
one layer, and inside it one expert, at a time. On a TPU a float32 matrix
multiplication runs in lower precision unless told otherwise: ``logits``
runs under ``jax.default_matmul_precision("highest")``.

The counts: ``num_experts_per_tok`` of ``num_experts`` experts take part
in a token; a decode step touches the share of a layer's experts that its
live tokens reach.
"""

from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp


# -- the adapter: the one part that touches the program -----------------

def model_config(config: dict):
    from ray_tpu.models import olmoe

    return olmoe.OlmoeConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], d_ff=config["intermediate_size"],
        n_experts=config["num_experts"],
        top_k=config["num_experts_per_tok"],
        norm_topk_prob=config["norm_topk_prob"],
        clip_qkv=config["clip_qkv"],
        rope_theta=float(config["rope_theta"]),
        rms_eps=float(config["rms_norm_eps"]),
        dtype={"bfloat16": "bfloat16", "float32": "float32"}[
            config["torch_dtype"]],
        tie_embeddings=config["tie_word_embeddings"])


def init_params(model_cfg, key):
    from ray_tpu.models import olmoe

    return olmoe.init_params(model_cfg, key)


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


def _routed_ffn(h, p, top_k, norm_topk_prob):
    """h [b, s, d] float32 -> the routed experts' sum, float32."""
    probs = jax.nn.softmax(h @ p["router"].astype(jnp.float32), axis=-1)
    kth = jnp.sort(probs, axis=-1)[..., -top_k]
    weight = jnp.where(probs >= kth[..., None], probs, 0.0)   # [b, s, E]
    if norm_topk_prob:
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)

    def one_expert(y, expert):
        gate, up, down, w = expert
        gate, up, down = (a.astype(jnp.float32) for a in (gate, up, down))
        out = (jax.nn.silu(h @ gate) * (h @ up)) @ down
        return y + jnp.where(w[..., None] > 0.0, w[..., None] * out, 0.0), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                        (p["wi_gate"], p["wi_up"], p["wo_e"],
                         jnp.moveaxis(weight, -1, 0)))
    return y


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "head_dim", "theta", "eps", "top_k",
    "norm_topk_prob", "qk_norm"))
def _layer(x, p, *, heads, kv_heads, head_dim, theta, eps, top_k,
           norm_topk_prob, qk_norm=True):
    """One decoder block on x [b, s, d] float32; p holds this layer's
    weights in their stored dtype. (``qk_norm`` false is not OLMoE: the
    CPU tests switch it off to show that the comparison would catch it.)"""
    f32 = lambda name: p[name].astype(jnp.float32)   # noqa: E731
    b, s, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    h = _rms_norm(x, f32("attn_norm"), eps)
    q, k, v = h @ f32("wq"), h @ f32("wk"), h @ f32("wv")
    if qk_norm:     # over the whole projection, before the split into heads
        q = _rms_norm(q, f32("q_norm"), eps)
        k = _rms_norm(k, f32("k_norm"), eps)
    q = _rope(q.reshape(b, s, heads, head_dim), pos, theta)
    k = _rope(k.reshape(b, s, kv_heads, head_dim), pos, theta)
    v = v.reshape(b, s, kv_heads, head_dim)
    rep = heads // kv_heads
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * head_dim ** -0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    attn = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    x = x + attn.reshape(b, s, heads * head_dim) @ f32("wo")
    h = _rms_norm(x, f32("mlp_norm"), eps)
    return x + _routed_ffn(h, p, top_k, norm_topk_prob)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, lm_head, *, eps):
    x = _rms_norm(x, final_norm.astype(jnp.float32), eps)
    return x @ lm_head.astype(jnp.float32)


@jax.jit
def _embed(embedding, tokens):
    return embedding[tokens].astype(jnp.float32)


def logits(config: dict, params: dict, tokens, **departures) -> jax.Array:
    """Float32 logits [b, s, vocab] of ``tokens`` [b, s]. ``departures``
    (``qk_norm=False``, ``norm_topk_prob=True``) are for the CPU tests
    that show the comparison fails on the wrong mathematics."""
    kw = dict(heads=config["num_attention_heads"],
              kv_heads=config["num_key_value_heads"],
              head_dim=config["head_dim"], theta=float(config["rope_theta"]),
              eps=float(config["rms_norm_eps"]),
              top_k=config["num_experts_per_tok"],
              norm_topk_prob=bool(config["norm_topk_prob"]))
    kw.update(departures)
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embedding"], tokens)
        for i in range(config["num_hidden_layers"]):
            x = _layer(x, jax.tree.map(lambda a: a[i], params["blocks"]),
                       **kw)
        head = (params["embedding"].T if config["tie_word_embeddings"]
                else params["lm_head"])
        return _head(x, params["final_norm"], head, eps=kw["eps"])


# -- the counts ----------------------------------------------------------

def _widths(m: dict) -> tuple:
    d = m["hidden_size"]
    q = m["num_attention_heads"] * m["head_dim"]
    kv = m["num_key_value_heads"] * m["head_dim"]
    return d, q, kv


def attention_params(m: dict) -> int:
    """A layer's attention projections (the norms' vectors left out)."""
    d, q, kv = _widths(m)
    return d * q + 2 * d * kv + q * d


def expert_params(m: dict) -> int:
    """One expert: gate, up and down."""
    return 3 * m["hidden_size"] * m["intermediate_size"]


def router_params(m: dict) -> int:
    return m["hidden_size"] * m["num_experts"]


def layer_params(m: dict) -> int:
    d, q, kv = _widths(m)
    norms = 2 * d + q + kv              # attn, mlp, q and k norms
    return (attention_params(m) + router_params(m)
            + m["num_experts"] * expert_params(m) + norms)


def total_params(m: dict) -> int:
    d, v = m["hidden_size"], m["vocab_size"]
    head = 0 if m["tie_word_embeddings"] else d * v
    return m["num_hidden_layers"] * layer_params(m) + d * v + head + d


def active_params(m: dict) -> int:
    """Parameters that take part in one token: ``num_experts_per_tok``
    experts a layer, not all of them (embedding row and head included)."""
    idle = (m["num_experts"] - m["num_experts_per_tok"]) * expert_params(m)
    return total_params(m) - m["num_hidden_layers"] * idle


def matmul_params_active(m: dict) -> int:
    """Parameters in a matrix multiplication of one token: the blocks
    with the chosen experts, and the output head (not the embedding
    lookup, not the norms)."""
    block = (attention_params(m) + router_params(m)
             + m["num_experts_per_tok"] * expert_params(m))
    return (m["num_hidden_layers"] * block
            + m["hidden_size"] * m["vocab_size"])


def kv_bytes_per_token_layer(m: dict) -> int:
    """Keys and values of one token in one layer, bf16."""
    return 2 * 2 * m["num_key_value_heads"] * m["head_dim"]


def attention_flops_fwd(m: dict, batch: int, seq: int) -> float:
    """Causal attention, forward, over ``batch`` sequences of ``seq``: QK^T
    and PV, 2 operations a multiply-add, half the square being masked."""
    q = m["num_attention_heads"] * m["head_dim"]
    return m["num_hidden_layers"] * batch * 0.5 * (4.0 * seq * seq * q)


def train_flops_per_token(m: dict, seq: int) -> float:
    """Forward plus backward (3 x forward) of a causal LM at sequence
    length ``seq``, the chosen experts only; recomputation not counted."""
    return (6.0 * matmul_params_active(m)
            + 3.0 * attention_flops_fwd(m, 1, seq) / seq)


def flash_train_cost(m: dict, batch: int, seq: int) -> dict:
    """What the flash kernels of one train step (forward, dq, dk/dv) must
    do at these widths: operations (backward = 2.5 x forward) and HBM
    bytes (q, k, v, o once forward; q, k, v, o, do read and dq, dk, dv
    written backward), bf16. No cell reads it yet."""
    _, q, kv = _widths(m)
    fwd = attention_flops_fwd(m, batch, seq)
    tok = batch * seq * m["num_hidden_layers"] * 2
    return {"flops": 3.5 * fwd,
            "bytes": tok * (2 * q + 2 * kv) + tok * (4 * q + 4 * kv)}


def attention_kv_bytes(m: dict, counters: dict) -> float:
    """Bytes of keys and values one decode step must read: every live
    token's (the counter ``live_kv_tokens_mean``) in every layer
    (``paged_attn_roofline``)."""
    return (kv_bytes_per_token_layer(m) * m["num_hidden_layers"]
            * counters.get("live_kv_tokens_mean", 0.0))


def experts_touched_share(m: dict, live_tokens: float) -> float:
    """The share of a layer's experts that ``live_tokens`` tokens reach
    when each picks ``num_experts_per_tok`` of ``num_experts`` uniformly:
    1 - (1 - k / E) ** n (routing of seeded random weights is close to
    uniform; ``tests/bench/test_bench_olmoe.py`` holds the formula to the
    program's own count)."""
    k, e = m["num_experts_per_tok"], m["num_experts"]
    return 1.0 - (1.0 - k / e) ** live_tokens


def decode_step_bytes(m: dict, counters: dict) -> float:
    """HBM bytes one decode step must move: attention and head weights
    (bf16) and the routers (float32) once, the live keys and values (the
    counter ``live_kv_tokens_mean``) once, and of the experts' weights the
    share a step touches, at the mean number of live slots (the counter
    ``occupancy_samples``)."""
    samples = counters.get("occupancy_samples") or [0]
    share = experts_touched_share(m, sum(samples) / len(samples))
    layers = m["num_hidden_layers"]
    always = (2.0 * (layers * attention_params(m)
                     + m["hidden_size"] * m["vocab_size"])
              + 4.0 * layers * router_params(m))
    experts = 2.0 * layers * m["num_experts"] * expert_params(m) * share
    return always + experts + attention_kv_bytes(m, counters)


def grouped_expert_cost(m: dict, n_out: int, pairs: float,
                        here_share=None):
    """What one call of the grouped expert kernel must do, at this
    family's widths (gate and up): ``families.grouped_expert_call_cost``.
    For ``grouped_expert_ffn_roofline``."""
    from benchmark.families import grouped_expert_call_cost

    return grouped_expert_call_cost(
        hidden=m["hidden_size"], width=m["intermediate_size"], held=m["num_experts"],
        total=m["num_experts"], up_stacks=2, n_out=n_out, pairs=pairs,
        here_share=here_share)


def expert_ffn_op(m: dict):
    """A predicate on a device operation's HLO text: true for the routed
    feed-forward's operations (router and experts), which are told from
    the rest of a program by the expert axis in a shape they read or
    write (an XLA fusion keeps no scope name in a trace: PERF.md, PR 28).
    For ``expert_ffn_share.*``."""
    e, d, f = m["num_experts"], m["hidden_size"], m["intermediate_size"]
    shapes = re.compile(
        r"\[(?:\d+,)*(?:"
        rf"{e},{d},{f}|{e},{f},{d}"        # the experts' weights
        rf"|{d},{e}"                       # the router
        rf"|\d+,{e},{f}|{e},\d+,{f}"       # [T, E, F], [E, T, F]
        r")\]")
    return lambda text: ("ragged-dot" in text
                         or shapes.search(text) is not None)
