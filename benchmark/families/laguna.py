"""Window-and-full-attention sparse-expert decoders served by
``ray_tpu.models.laguna`` (poolside Laguna-S-2.1, ``model_type``
``laguna``): the adapter from the published Hugging Face keys to the
program's config class, the plain reference of the block, and its
operation and byte counts (``benchmark/families/__init__.py`` says what a
family is).

The reference follows the published ``config.json``, layer by layer
(``layer_types``, ``num_attention_heads_per_layer``, ``mlp_layer_types``):
RMSNorm in float32; q, k and v with the LAYER's number of query heads over
8 KV heads; rotary of the layer's kind (``rope_parameters``): a
``full_attention`` layer YaRN-scaled frequencies over the first
``partial_rotary_factor`` of each head, sin and cos times
``attention_factor``, the rest of the head untouched; a
``sliding_attention`` layer plain rotary over the whole head; causal
softmax attention, in a sliding layer over keys ``i - sliding_window < j
<= i``; each head's output times its gate (``gating: per-head``); ``wo``;
then a dense SwiGLU (``mlp_layer_types`` ``dense``) or the routed experts
(``num_experts_per_tok`` of the router's experts, ``norm_topk_prob``,
times ``moe_routed_scaling_factor``, weights on the experts' outputs)
plus the shared expert; final RMSNorm; untied head.

What ``config.json`` has no key for is a NAMED DEPARTURE of ``logits``,
with the reading taken as its default, so that the other reading is one
argument away (the configuration file lists each under ``assumed``):

- ``gate="sigmoid"``: the per-head gate is ``sigmoid(h @ wg)`` of the
  sublayer's normed input ``h``, no bias (arXiv:2505.06708's head-wise
  output gate); ``gate="none"`` leaves it out;
- ``scores="softmax"``: the router's scores are a float32 softmax over
  all its logits before the top-k; ``scores="sigmoid"`` is the other
  common reading.

and, for the CPU tests and the one check by hand on the chip that show
the comparison sees each mechanism: ``window=None`` (a sliding layer
attends over everything), ``routing_scale=1.0``, ``yarn=False`` (plain
rotary at ``rope_theta`` over the same part of the head, no factor).

THE SHARE. ``num_experts`` in a configuration file is the number of
experts HELD here, of ``expert_share.num_experts_total`` that the router
scores, starting at ``expert_share.index x num_experts``: the reference
routes over all of them and adds the held experts' part, as the program
does and as one chip of an expert-parallel group would before the
exchange. ``vocab_size`` is likewise the slice held here. A file with no
``expert_share`` holds every expert.

It reads the program's parameter layout, which is data, not code
(``params["blocks"]`` maps ``layers<first>[-<last>]`` to that run of
identical layers' weights stacked on a leading axis; ``wqkv`` holds the
columns q | k | v), and imports nothing from the program. The sum over a
token's chosen experts is a loop over the held experts, each applied to
every token and kept where it is among the token's chosen (as
``families/olmoe.py`` does, and for its reason). On a TPU a float32
matrix multiplication runs in lower precision unless told otherwise:
``logits`` runs under ``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import functools
import math
import re

import jax
import jax.numpy as jnp


# -- the adapter: the one part that touches the program -----------------

def _share(config: dict) -> tuple:
    """(experts the router scores, index of the first one held here)."""
    share = config.get("expert_share")
    if share is None:
        return config["num_experts"], 0
    return share["num_experts_total"], share["index"] * config["num_experts"]


def model_config(config: dict):
    from ray_tpu.models import laguna

    heads = dict(zip(config["layer_types"],
                     config["num_attention_heads_per_layer"]))
    full = config["rope_parameters"]["full_attention"]
    sliding = config["rope_parameters"]["sliding_attention"]
    total, first = _share(config)
    return laguna.LagunaConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        layer_types=tuple(config["layer_types"]),
        mlp_only_layers=tuple(config["mlp_only_layers"]),
        n_heads=heads["full_attention"],
        n_heads_sliding=heads["sliding_attention"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], window=config["sliding_window"],
        d_ff=config["intermediate_size"],
        d_expert=config["moe_intermediate_size"],
        d_shared=config["shared_expert_intermediate_size"],
        n_experts=total, n_experts_held=config["num_experts"],
        first_expert=first, top_k=config["num_experts_per_tok"],
        norm_topk_prob=config["norm_topk_prob"],
        routed_scale=float(config["moe_routed_scaling_factor"]),
        rope_theta=float(full["rope_theta"]),
        partial_rotary=float(full["partial_rotary_factor"]),
        yarn_factor=float(full["factor"]),
        yarn_original_len=full["original_max_position_embeddings"],
        yarn_beta_fast=float(full["beta_fast"]),
        yarn_beta_slow=float(full["beta_slow"]),
        yarn_attention_factor=float(full["attention_factor"]),
        rope_theta_sliding=float(sliding["rope_theta"]),
        rms_eps=float(config["rms_norm_eps"]),
        dtype={"bfloat16": "bfloat16", "float32": "float32"}[
            config.get("torch_dtype", "bfloat16")],
        tie_embeddings=config["tie_word_embeddings"])


def init_params(model_cfg, key):
    from ray_tpu.models import laguna

    return laguna.init_params(model_cfg, key)


# -- the plain reference -------------------------------------------------

def _rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _yarn_inv_freq(dim, base, factor, original, beta_fast, beta_slow):
    """Hugging Face ``_compute_yarn_parameters``: pair i keeps its
    frequency below ``low``, has it divided by ``factor`` above ``high``,
    and a linear blend between."""
    def correction_dim(rotations):
        return (dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    extrapolation = 1.0 / base ** (2.0 * i / dim)
    interpolation = extrapolation / factor
    ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return interpolation * ramp + extrapolation * (1.0 - ramp)


def _rope(x, positions, inv_freq, factor):
    """x [b, s, h, hd]: rotate pairs (i, i + r/2) of the first r = 2 x
    len(inv_freq) features, sin and cos times ``factor``; the rest pass."""
    r = 2 * inv_freq.shape[0]
    ang = positions[..., None].astype(jnp.float32) * inv_freq
    sin = jnp.sin(ang)[:, :, None, :] * factor
    cos = jnp.cos(ang)[:, :, None, :] * factor
    x1, x2, rest = x[..., :r // 2], x[..., r // 2:r], x[..., r:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1)


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def _routed_ffn(h, p, *, top_k, norm_topk_prob, routing_scale, scores,
                first):
    """h [b, s, d] float32 -> the HELD routed experts' sum, float32: the
    router scores every expert; experts ``first`` onwards, as many as the
    stacks hold, add their part."""
    logits = h @ p["router"].astype(jnp.float32)
    probs = (jax.nn.softmax(logits, axis=-1) if scores == "softmax"
             else jax.nn.sigmoid(logits))
    kth = jnp.sort(probs, axis=-1)[..., -top_k]
    weight = jnp.where(probs >= kth[..., None], probs, 0.0)   # [b, s, E]
    if norm_topk_prob:
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    weight = weight * routing_scale
    held = p["wi_gate"].shape[0]
    weight = weight[..., first:first + held]

    def one_expert(y, expert):
        gate, up, down, w = expert
        out = _swiglu(h, *(a.astype(jnp.float32) for a in (gate, up, down)))
        return y + jnp.where(w[..., None] > 0.0, w[..., None] * out, 0.0), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                        (p["wi_gate"], p["wi_up"], p["wo_e"],
                         jnp.moveaxis(weight, -1, 0)))
    return y


@functools.partial(jax.jit, static_argnames=(
    "kv_heads", "head_dim", "eps", "window", "rope", "gate", "top_k",
    "norm_topk_prob", "routing_scale", "scores", "first"))
def _layer(x, p, *, kv_heads, head_dim, eps, window, rope, gate, top_k,
           norm_topk_prob, routing_scale, scores, first):
    """One decoder layer on x [b, s, d] float32; p holds this layer's
    weights in their stored dtype. ``window``: None, or the keys a query
    sees. ``rope``: ("yarn", dim, base, factor, original, beta_fast,
    beta_slow, attention_factor) or ("plain", dim, base)."""
    f32 = lambda name: p[name].astype(jnp.float32)   # noqa: E731
    b, s, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    h = _rms_norm(x, f32("attn_norm"), eps)
    kvdim = kv_heads * head_dim
    qkv = h @ f32("wqkv")
    qdim = qkv.shape[-1] - 2 * kvdim
    heads = qdim // head_dim
    q = qkv[..., :qdim].reshape(b, s, heads, head_dim)
    k = qkv[..., qdim:qdim + kvdim].reshape(b, s, kv_heads, head_dim)
    v = qkv[..., qdim + kvdim:].reshape(b, s, kv_heads, head_dim)
    if rope[0] == "yarn":
        inv_freq, factor = _yarn_inv_freq(*rope[1:7]), rope[7]
    else:
        dim, base = rope[1:]
        inv_freq = 1.0 / base ** (jnp.arange(0, dim, 2, jnp.float32) / dim)
        factor = 1.0
    q, k = _rope(q, pos, inv_freq, factor), _rope(k, pos, inv_freq, factor)
    rep = heads // kv_heads
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    att = jnp.einsum("bqhd,bkhd->bhqk", q, k) * head_dim ** -0.5
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    seen = j <= i
    if window is not None:
        seen = seen & (j > i - window)
    att = jnp.where(seen, att, -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(att, -1), v)
    if gate == "sigmoid":
        out = out * jax.nn.sigmoid(h @ f32("wg"))[..., None]
    x = x + out.reshape(b, s, qdim) @ f32("wo")
    h = _rms_norm(x, f32("mlp_norm"), eps)
    if "w_gate" in p:
        return x + _swiglu(h, f32("w_gate"), f32("w_up"), f32("w_down"))
    routed = _routed_ffn(h, p, top_k=top_k, norm_topk_prob=norm_topk_prob,
                         routing_scale=routing_scale, scores=scores,
                         first=first)
    return x + routed + _swiglu(h, f32("ws_gate"), f32("ws_up"),
                                f32("ws_down"))


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, lm_head, *, eps):
    x = _rms_norm(x, final_norm.astype(jnp.float32), eps)
    return x @ lm_head.astype(jnp.float32)


@jax.jit
def _embed(embedding, tokens):
    return embedding[tokens].astype(jnp.float32)


def _layers(blocks: dict):
    """Each layer's weights in layer order, from the runs' stacks."""
    first = lambda key: int(re.match(r"layers(\d+)", key).group(1))  # noqa: E731
    for key in sorted(blocks, key=first):
        stack = blocks[key]
        for i in range(jax.tree.leaves(stack)[0].shape[0]):
            yield jax.tree.map(lambda a: a[i], stack)


def logits(config: dict, params: dict, tokens, *, gate="sigmoid",
           scores="softmax", window="published", routing_scale=None,
           yarn=True) -> jax.Array:
    """Float32 logits [b, s, vocab] of ``tokens`` [b, s], one layer at a
    time. The keyword arguments are the named departures of the module
    docstring; their defaults are the configuration's reading."""
    full = config["rope_parameters"]["full_attention"]
    sliding = config["rope_parameters"]["sliding_attention"]
    hd = config["head_dim"]
    rot = int(hd * full["partial_rotary_factor"])
    rope = {
        "full_attention": (
            "yarn", rot, float(full["rope_theta"]), float(full["factor"]),
            full["original_max_position_embeddings"],
            float(full["beta_fast"]), float(full["beta_slow"]),
            float(full["attention_factor"])) if yarn else (
            "plain", rot, float(full["rope_theta"])),
        "sliding_attention": (
            "plain", int(hd * sliding["partial_rotary_factor"]),
            float(sliding["rope_theta"]))}
    if window == "published":
        window = config["sliding_window"]
    if routing_scale is None:
        routing_scale = float(config["moe_routed_scaling_factor"])
    kw = dict(kv_heads=config["num_key_value_heads"], head_dim=hd,
              eps=float(config["rms_norm_eps"]), gate=gate,
              top_k=config["num_experts_per_tok"],
              norm_topk_prob=bool(config["norm_topk_prob"]),
              routing_scale=routing_scale, scores=scores,
              first=_share(config)[1])
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embedding"], tokens)
        for kind, p in zip(config["layer_types"],
                           _layers(params["blocks"]), strict=True):
            x = _layer(x, p, rope=rope[kind], window=(
                window if kind == "sliding_attention" else None), **kw)
        head = (params["embedding"].T if config["tie_word_embeddings"]
                else params["lm_head"])
        return _head(x, params["final_norm"], head, eps=kw["eps"])


# -- the counts ----------------------------------------------------------

def attention_layer_counts(m: dict) -> tuple:
    """(full layers, sliding layers)."""
    sliding = sum(t == "sliding_attention" for t in m["layer_types"])
    return len(m["layer_types"]) - sliding, sliding


def attention_params(m: dict, heads: int) -> int:
    """A layer's attention weights at ``heads`` query heads: q | k | v,
    the per-head gate and ``wo`` (the norm's vector left out)."""
    d, hd = m["hidden_size"], m["head_dim"]
    q, kv = heads * hd, m["num_key_value_heads"] * hd
    return d * (q + 2 * kv) + d * heads + q * d


def expert_params(m: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def shared_expert_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["shared_expert_intermediate_size"]


def router_params(m: dict) -> int:
    return m["hidden_size"] * _share(m)[0]


def dense_mlp_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def _sparse_layers(m: dict) -> int:
    return sum(t == "sparse" for t in m["mlp_layer_types"])


def total_params(m: dict) -> int:
    """Parameters HELD here: ``num_experts`` experts a sparse layer, the
    ``vocab_size`` rows of the embedding and columns of the head."""
    d, v = m["hidden_size"], m["vocab_size"]
    attention = sum(attention_params(m, h)
                    for h in m["num_attention_heads_per_layer"])
    sparse = _sparse_layers(m)
    dense = len(m["mlp_layer_types"]) - sparse
    ffn = (dense * dense_mlp_params(m) + sparse * (
        m["num_experts"] * expert_params(m) + shared_expert_params(m)
        + router_params(m)))
    norms = 2 * d * len(m["layer_types"]) + d
    head = 0 if m["tie_word_embeddings"] else d * v
    return attention + ffn + norms + d * v + head


def kv_bytes_per_token_layer(m: dict) -> int:
    """Keys and values of one token in one layer, bf16."""
    return 2 * 2 * m["num_key_value_heads"] * m["head_dim"]


def experts_touched_share(m: dict, live_tokens: float) -> float:
    """The share of the HELD experts that ``live_tokens`` tokens reach
    when each picks ``num_experts_per_tok`` of all the router's experts
    uniformly: 1 - (1 - k / E) ** n."""
    k, e = m["num_experts_per_tok"], _share(m)[0]
    return 1.0 - (1.0 - k / e) ** live_tokens


def _live_slots(counters: dict) -> float:
    samples = counters.get("occupancy_samples") or [0]
    return sum(samples) / len(samples)


def attention_kv_bytes(m: dict, counters: dict) -> float:
    """Bytes of keys and values one decode step must read: in a full
    layer every live token's (the counter ``live_kv_tokens_mean``), in a
    sliding layer ``sliding_window`` tokens' for each live slot, or all
    of them where that is fewer. (Exact where every live context is past
    the window, or none is; between, the sliding layers' part is an
    upper bound no greater than the window's.)"""
    full, sliding = attention_layer_counts(m)
    live = counters.get("live_kv_tokens_mean", 0.0)
    seen = min(live, m["sliding_window"] * _live_slots(counters))
    return kv_bytes_per_token_layer(m) * (full * live + sliding * seen)


def decode_step_bytes(m: dict, counters: dict) -> float:
    """HBM bytes one decode step must move: the attention, dense,
    shared-expert and head weights (bf16) and the routers (float32)
    once; of the held experts' weights the share that the live tokens
    reach (at the mean number of live slots: ``occupancy_samples``); the
    keys and values of ``attention_kv_bytes`` once. The engine reads
    every held expert whatever the routing, so against this count its
    share of the roofline reads low, never high."""
    sparse = _sparse_layers(m)
    dense = len(m["mlp_layer_types"]) - sparse
    attention = sum(attention_params(m, h)
                    for h in m["num_attention_heads_per_layer"])
    always = (2.0 * (attention + dense * dense_mlp_params(m)
                     + sparse * shared_expert_params(m)
                     + m["hidden_size"] * m["vocab_size"])
              + 4.0 * sparse * router_params(m))
    experts = (2.0 * sparse * m["num_experts"] * expert_params(m)
               * experts_touched_share(m, _live_slots(counters)))
    return always + experts + attention_kv_bytes(m, counters)


def train_flops_per_token(m: dict, seq: int):
    """No training path for this family (a chip's share of the experts
    trains only with the exchange this cut leaves out)."""
    return None


def flash_train_cost(m: dict, batch: int, seq: int):
    return None


def grouped_expert_cost(m: dict, n_out: int, pairs: float,
                        here_share=None):
    """What one call of the grouped expert kernel must do, at this
    family's widths (gate and up): ``families.grouped_expert_call_cost``.
    For ``grouped_expert_ffn_roofline``."""
    from benchmark.families import grouped_expert_call_cost

    return grouped_expert_call_cost(
        hidden=m["hidden_size"], width=m["moe_intermediate_size"], held=m["num_experts"],
        total=_share(m)[0], up_stacks=2, n_out=n_out, pairs=pairs,
        here_share=here_share)


def expert_ffn_op(m: dict):
    """A predicate on a device operation's HLO text: true for the ROUTED
    feed-forward's operations (router and held experts; the shared
    expert is a dense matmul like the attention's), told from the rest
    of a program by the expert axis in a shape they read or write. For
    ``expert_ffn_share.*``."""
    e, total = m["num_experts"], _share(m)[0]
    d, f = m["hidden_size"], m["moe_intermediate_size"]
    shapes = re.compile(
        r"\[(?:\d+,)*(?:"
        rf"{e},{d},{f}|{e},{f},{d}"        # the held experts' weights
        rf"|{d},{total}"                   # the router
        rf"|\d+,{e},{f}|{e},\d+,{f}"       # [T, H, F], [H, T, F]
        r")\]")
    return lambda text: ("ragged-dot" in text
                         or shapes.search(text) is not None)
