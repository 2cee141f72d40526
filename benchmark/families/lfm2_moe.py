"""Hybrid decoders of gated short convolutions and grouped-query attention
with two dense layers and then routed experts, served by
``ray_tpu.models.lfm2_moe`` (Liquid AI LFM2-8B-A1B, ``model_type``
``lfm2_moe``): the adapter from the published Hugging Face keys to the
program's config class, the plain reference of the layer, and its byte
counts (``benchmark/families/__init__.py`` says what a family is).

The reference follows the published ``config.json``. With ``h`` the
stream, RMSNorm in float32 with ``norm_eps``, no bias anywhere:

- in: ``h = embedding[ids]``;
- every layer i: ``h <- h + Op_i(rms(h, norm_i))``, then ``h <- h +
  Ffn_i(rms(h, ffn_norm_i))``;
- ``Op_i``, ``layer_types[i] == "conv"`` (the gated short convolution,
  ``conv_L_cache`` K taps): ``B | C | u = x W_in`` (three equal parts);
  ``g = B * u``; ``c_t = sum_{j<K} w[:, j] g_{t-K+1+j}`` (a depthwise
  causal filter, zeros before the sequence's start, no bias, no
  activation): computed here as the K-tap sum over the WHOLE sequence
  (the program keeps the last ``K - 1`` rows of ``g`` a sequence and a
  page); ``(C * c) W_out``;
- ``Op_i``, ``"full_attention"``: ``num_attention_heads`` query heads on
  ``num_key_value_heads`` KV heads of ``hidden_size /
  num_attention_heads``; an RMS norm over each head of q and of k, then
  rotate-half rotary over the whole head at ``rope_theta``; a causal
  softmax over ALL keys of ``q k^T / sqrt(head)``, a block of queries at
  a time; ``W_o``;
- ``Ffn_i``, ``i < num_dense_layers``: ``(silu(x W_gate) * (x W_up))
  W_down`` at ``intermediate_size``;
- ``Ffn_i``, the others: ``s = sigmoid(x W_r)`` over ``num_experts``; the
  ``num_experts_per_tok`` largest of ``s + expert_bias`` are CHOSEN;
  their weights ``s`` itself over ``sum + 1e-6`` (``norm_topk_prob``),
  times ``routed_scaling_factor``; expert e is a SwiGLU at
  ``moe_intermediate_size``, one expert after another;
- out: ``logits = rms(h, final_norm) embedding^T`` (the head is tied).

What ``config.json`` leaves to the family's convention, or the catalog
row leaves out, is a NAMED DEPARTURE of ``logits``, with the reading
taken as its default, so that the other reading is one argument away (the
configuration file lists each under ``assumed``); each alone must read
not correct (the CPU tests, and ``scripts/check_seeds.py`` on the chip):

- ``order="bcx"``: the input projection's thirds are B | C | u;
  ``"x_first"`` reads them u | B | C;
- ``conv_act="none"``; ``"silu"`` puts a SiLU on the filter's output (as
  a Mamba-2 convolution has);
- ``qk_norm="head"``; ``"none"`` leaves the per-head norms out;
- ``norm_place="before_rope"``; ``"after_rope"`` rotates first;
- ``scores="sigmoid"``; ``"softmax"`` scores by a softmax over all the
  router's logits;
- ``bias="choice"``: ``expert_bias`` moves the choice and no weight;
  ``"none"`` leaves it out of the choice;
- ``weights="scores"``; ``"with_bias"`` takes the chosen weights from ``s
  + expert_bias``;
- ``experts="swiglu"``; ``"geglu"`` gates by a GELU, dense and routed
  alike;
- ``dense_layers=None``: ``num_dense_layers``; ``dense_layers=0`` reads
  the leading layers as routed like the rest: their ``intermediate_size``
  columns as experts of ``moe_intermediate_size`` each (7,168 = 4 x
  1,792: the four a token takes), at the weights an indifferent router
  gives, one over their number each.

It reads the program's parameter layout, which is data, not code
(``params["blocks"]`` maps ``layers<first>[-<last>]`` to that run of
identical layers' weights stacked on a leading axis; ``in_proj`` holds
the columns B | C | u, ``wqkv`` q | k | v), and imports nothing from the
program. One layer's weights are converted to float32 at a time, the
experts one expert at a time, the head in row blocks. On a TPU a float32
matrix multiplication runs in lower precision unless told otherwise:
``logits`` runs under ``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp

HEAD_BLOCK = 8192           # rows of the tied head converted at once
QUERY_BLOCK = 512           # queries that attend at once
ROUTE_EPS = 1e-6            # on the chosen scores' sum


# -- the adapter: the one part that touches the program -----------------

def _head_dim(config: dict) -> int:
    return config["hidden_size"] // config["num_attention_heads"]


def model_config(config: dict):
    try:
        from ray_tpu.models import lfm2_moe
    except ImportError as e:    # a program from before this family's block
        raise SystemExit("benchmark: the program states no block for model "
                         f"family 'lfm2_moe' ({e}); no result") from e

    if not (not config["conv_bias"] and config["use_expert_bias"]
            and config["norm_topk_prob"]
            and len(config["layer_types"]) == config["num_hidden_layers"]):
        raise ValueError(
            "the program states the published LFM2-MoE layers only: no "
            "convolution bias, an expert bias, the chosen weights "
            "normalised, an entry of layer_types a layer")
    return lfm2_moe.Lfm2MoeConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        layer_types=tuple(config["layer_types"]),
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"], head_dim=_head_dim(config),
        rope_theta=float(config["rope_theta"]),
        conv_taps=config["conv_L_cache"],
        state_chunk=config["system"]["page_size"],
        d_ff=config["intermediate_size"],
        n_dense_layers=config["num_dense_layers"],
        d_expert=config["moe_intermediate_size"],
        n_experts=config["num_experts"], top_k=config["num_experts_per_tok"],
        norm_topk_prob=config["norm_topk_prob"],
        routed_scale=float(config["routed_scaling_factor"]),
        rms_eps=float(config["norm_eps"]),
        dtype={"bfloat16": "bfloat16", "float32": "float32"}[
            config.get("torch_dtype", "bfloat16")],
        tie_embeddings=config.get("tie_word_embeddings", True))


def init_params(model_cfg, key):
    from ray_tpu.models import lfm2_moe

    return lfm2_moe.init_params(model_cfg, key)


# -- the plain reference -------------------------------------------------

def _rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rope(x, theta):
    """x [b, s, h, hd] at positions 0..s-1; rotate pairs (i, i + hd/2)."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    sin, cos = jnp.sin(ang)[None, :, None, :], jnp.cos(ang)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _f32(p: dict, *names):
    return (p[name].astype(jnp.float32) for name in names)


@functools.partial(jax.jit, static_argnames=("eps", "order", "conv_act"))
def _short_conv(x, p, *, eps, order, conv_act):
    """A ``conv`` layer's operator, from x [b, s, d]: the K-tap sum over
    the whole sequence."""
    norm, in_proj, conv_w, out_proj = _f32(p, "norm", "in_proj", "conv_w",
                                           "out_proj")
    s = x.shape[1]
    thirds = jnp.split(_rms_norm(x, norm, eps) @ in_proj, 3, axis=-1)
    b, c, u = thirds if order == "bcx" else (thirds[1], thirds[2], thirds[0])
    g = b * u
    taps = conv_w.shape[-1]
    padded = jnp.pad(g, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = sum(padded[:, j:j + s] * conv_w[:, j] for j in range(taps))
    if conv_act == "silu":
        conv = jax.nn.silu(conv)
    return (c * conv) @ out_proj


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "head_dim", "eps", "theta", "qk_norm",
    "norm_place"))
def _attention(x, p, *, heads, kv_heads, head_dim, eps, theta, qk_norm,
               norm_place):
    """A ``full_attention`` layer's operator, from x [b, s, d]: a masked
    softmax over all keys."""
    norm, wqkv, wo, q_norm, k_norm = _f32(p, "norm", "wqkv", "wo", "q_norm",
                                          "k_norm")
    b, s, _ = x.shape
    qkv = _rms_norm(x, norm, eps) @ wqkv
    qdim, kvdim = heads * head_dim, kv_heads * head_dim
    q = qkv[..., :qdim].reshape(b, s, heads, head_dim)
    k = qkv[..., qdim:qdim + kvdim].reshape(b, s, kv_heads, head_dim)
    v = qkv[..., qdim + kvdim:].reshape(b, s, kv_heads, head_dim)

    def normed(q, k):
        if qk_norm == "none":
            return q, k
        return _rms_norm(q, q_norm, eps), _rms_norm(k, k_norm, eps)

    if norm_place == "before_rope":
        q, k = normed(q, k)
        q, k = _rope(q, theta), _rope(k, theta)
    else:
        q, k = normed(_rope(q, theta), _rope(k, theta))
    rep = heads // kv_heads
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    positions = jnp.arange(s)

    def block(first):
        """``QUERY_BLOCK`` queries against every key (float32 scores of a
        4k prompt whole are 1.7 GB beside the served weights)."""
        rows = jnp.minimum(first + jnp.arange(QUERY_BLOCK), s - 1)
        att = jnp.einsum("bqhd,bkhd->bhqk", q[:, rows], k) * head_dim ** -0.5
        seen = positions[None, :] <= rows[:, None]
        att = jax.nn.softmax(jnp.where(seen, att, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", att, v)

    out = jax.lax.map(block, jnp.arange(0, s, QUERY_BLOCK))
    # (a tail block repeats the last row; the slice drops the repeats)
    out = jnp.moveaxis(out, 0, 1).reshape(b, -1, qdim)[:, :s]
    return out @ wo


def _gate(a, experts: str):
    return (jax.nn.silu(a) if experts == "swiglu"
            else jax.nn.gelu(a, approximate=False))


@functools.partial(jax.jit, static_argnames=("eps", "experts", "split"))
def _dense(x, p, *, eps, experts, split):
    """A dense layer's feed-forward, from x [b, s, d]. ``split``: None,
    or the number of equal experts the columns are read as, each at the
    weight an indifferent router gives (the departure ``dense_layers``)."""
    norm, w_gate, w_up, w_down = _f32(p, "ffn_norm", "w_gate", "w_up",
                                      "w_down")
    h = _rms_norm(x, norm, eps)
    out = (_gate(h @ w_gate, experts) * (h @ w_up)) @ w_down
    return out if split is None else out / split


@functools.partial(jax.jit, static_argnames=(
    "eps", "top_k", "scale", "scores", "bias", "weights", "experts"))
def _routed(x, p, *, eps, top_k, scale, scores, bias, weights, experts):
    """A routed layer's feed-forward, from x [b, s, d]: the router scores
    every expert, the chosen ones add their part, one expert at a
    time."""
    norm, router, router_bias = _f32(p, "ffn_norm", "router", "router_bias")
    h = _rms_norm(x, norm, eps)
    logits = h @ router
    s = (jax.nn.sigmoid(logits) if scores == "sigmoid"
         else jax.nn.softmax(logits, axis=-1))
    ranked = s if bias == "none" else s + router_bias
    kth = jnp.sort(ranked, axis=-1)[..., -top_k]
    chosen = ranked >= kth[..., None]                        # [b, s, E]
    taken = jnp.where(chosen, ranked if weights == "with_bias" else s, 0.0)
    weight = (taken / (jnp.sum(taken, axis=-1, keepdims=True) + ROUTE_EPS)
              * scale)

    def one_expert(y, expert):
        gate, up, down, w, on = expert
        out = (_gate(h @ gate.astype(jnp.float32), experts)
               * (h @ up.astype(jnp.float32))) @ down.astype(jnp.float32)
        return y + jnp.where(on[..., None], w[..., None] * out, 0.0), None

    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(h),
        (p["wi_gate"], p["wi_up"], p["wo_e"], jnp.moveaxis(weight, -1, 0),
         jnp.moveaxis(chosen, -1, 0)))
    return routed


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, embedding, *, eps):
    """The tied head: the normed stream against the embedding's rows, a
    block of rows at a time."""
    x = _rms_norm(x, final_norm.astype(jnp.float32), eps)
    rows = embedding.shape[0]
    return jnp.concatenate(
        [x @ embedding[r:r + HEAD_BLOCK].astype(jnp.float32).T
         for r in range(0, rows, HEAD_BLOCK)], axis=-1)


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


def logits(config: dict, params: dict, tokens, *, order="bcx",
           conv_act="none", qk_norm="head", norm_place="before_rope",
           scores="sigmoid", bias="choice", weights="scores",
           experts="swiglu", dense_layers=None) -> jax.Array:
    """Float32 logits [b, s, vocab] of ``tokens`` [b, s], one layer at a
    time. The keyword arguments are the named departures of the module
    docstring; their defaults are the configuration's reading."""
    for name, value, known in (
            ("order", order, ("bcx", "x_first")),
            ("conv_act", conv_act, ("none", "silu")),
            ("qk_norm", qk_norm, ("head", "none")),
            ("norm_place", norm_place, ("before_rope", "after_rope")),
            ("scores", scores, ("sigmoid", "softmax")),
            ("bias", bias, ("choice", "none")),
            ("weights", weights, ("scores", "with_bias")),
            ("experts", experts, ("swiglu", "geglu"))):
        if value not in known:
            raise ValueError(f"{name} is one of {known}, not {value!r}")
    eps = float(config["norm_eps"])
    conv_kw = dict(eps=eps, order=order, conv_act=conv_act)
    attn_kw = dict(
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head_dim=_head_dim(config),
        eps=eps, theta=float(config["rope_theta"]), qk_norm=qk_norm,
        norm_place=norm_place)
    routed_kw = dict(eps=eps, top_k=config["num_experts_per_tok"],
                     scale=float(config["routed_scaling_factor"]),
                     scores=scores, bias=bias, weights=weights,
                     experts=experts)
    dense = (config["num_dense_layers"] if dense_layers is None
             else dense_layers)
    as_experts = config["intermediate_size"] // config[
        "moe_intermediate_size"]
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embedding"], tokens)
        for i, (kind, p) in enumerate(zip(
                config["layer_types"], _layers(params["blocks"]),
                strict=True)):
            x = x + (_short_conv(x, p, **conv_kw) if kind == "conv"
                     else _attention(x, p, **attn_kw))
            if "router" in p:
                x = x + _routed(x, p, **routed_kw)
            else:
                x = x + _dense(x, p, eps=eps, experts=experts,
                               split=None if i < dense else as_experts)
        head = (params["embedding"] if config.get("tie_word_embeddings", True)
                else params["lm_head"].T)
        return _head(x, params["final_norm"], head, eps=eps)


# -- the counts ----------------------------------------------------------

def layer_counts(m: dict) -> tuple:
    """(``conv`` layers, ``full_attention`` layers, dense layers among
    them all)."""
    kinds = m["layer_types"]
    return (kinds.count("conv"), kinds.count("full_attention"),
            min(m["num_dense_layers"], len(kinds)))


def conv_params(m: dict) -> int:
    """in_proj, out_proj, the filter and the operator's norm."""
    d = m["hidden_size"]
    return 3 * d * d + d * d + d * m["conv_L_cache"] + d


def attention_params(m: dict) -> int:
    """wqkv, wo, the two per-head norms and the operator's norm."""
    d, hd = m["hidden_size"], _head_dim(m)
    q, kv = m["num_attention_heads"] * hd, m["num_key_value_heads"] * hd
    return d * (q + 2 * kv) + q * d + 2 * hd + d


def dense_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def expert_params(m: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def router_params(m: dict) -> int:
    """The router and its bias, float32."""
    return (m["hidden_size"] + 1) * m["num_experts"]


def total_params(m: dict) -> int:
    d, v = m["hidden_size"], m["vocab_size"]
    convs, attention, dense = layer_counts(m)
    layers = convs + attention
    routed = layers - dense
    head = 0 if m.get("tie_word_embeddings", True) else d * v
    return (convs * conv_params(m) + attention * attention_params(m)
            + dense * dense_params(m)
            + routed * (m["num_experts"] * expert_params(m)
                        + router_params(m))
            + layers * d + d * v + head + d)


def state_bytes_per_slot_layer(m: dict) -> int:
    """What one sequence, and one page, keeps in one ``conv`` layer: the
    bf16 tail [taps - 1, hidden] (the configuration's
    ``assumed.state_dtype``)."""
    return 2 * (m["conv_L_cache"] - 1) * m["hidden_size"]


def kv_bytes_per_token_layer(m: dict) -> int:
    """Keys and values of one token in one ``full_attention`` layer,
    bf16, two heads of 64 a row of the pool."""
    return 2 * 2 * m["num_key_value_heads"] * _head_dim(m)


def _live_slots(counters: dict) -> float:
    samples = counters.get("occupancy_samples") or [0]
    return sum(samples) / len(samples)


def attention_kv_bytes(m: dict, counters: dict) -> float:
    """Bytes of keys and values one decode step must read: every live
    token's (the counter ``live_kv_tokens_mean``) in every
    ``full_attention`` layer."""
    return (kv_bytes_per_token_layer(m) * layer_counts(m)[1]
            * counters.get("live_kv_tokens_mean", 0.0))


def conv_state_bytes(m: dict, counters: dict) -> float:
    """Bytes of recurrent state one decode step must move: every live
    slot's tail (the mean number of live slots) read once and written
    once in every ``conv`` layer."""
    return (2.0 * state_bytes_per_slot_layer(m) * layer_counts(m)[0]
            * _live_slots(counters))


def experts_touched_share(m: dict, live_tokens: float) -> float:
    """The share of the experts that ``live_tokens`` tokens reach when
    each picks ``num_experts_per_tok`` of them uniformly: 1 - (1 - k / E)
    ** n (the formula the other routed families use)."""
    k, e = m["num_experts_per_tok"], m["num_experts"]
    return 1.0 - (1.0 - k / e) ** live_tokens


def decode_step_bytes(m: dict, counters: dict) -> float:
    """HBM bytes one decode step must move: the operators', the dense
    layers' and the tied embedding's weights (bf16) and the routers
    (float32) once; of the experts' weights the share that the live
    tokens reach (at the mean number of live slots); the live keys and
    values once; the live slots' tails read and written once."""
    convs, attention, dense = layer_counts(m)
    routed = convs + attention - dense
    always = (2.0 * (convs * conv_params(m)
                     + attention * attention_params(m)
                     + dense * dense_params(m)
                     + m["hidden_size"] * m["vocab_size"])
              + 4.0 * routed * router_params(m))
    experts = (2.0 * routed * m["num_experts"] * expert_params(m)
               * experts_touched_share(m, _live_slots(counters)))
    return (always + experts + attention_kv_bytes(m, counters)
            + conv_state_bytes(m, counters))


def train_flops_per_token(m: dict, seq: int):
    """No training path for this family (the trainer runs one block
    repeated)."""
    return None


def flash_train_cost(m: dict, batch: int, seq: int):
    return None


def grouped_expert_cost(m: dict, n_out: int, pairs: float,
                        here_share=None):
    """What one call of the grouped expert kernel must do, at this
    family's widths (gate and up: two stacks):
    ``families.grouped_expert_call_cost``. For
    ``grouped_expert_ffn_roofline``."""
    from benchmark.families import grouped_expert_call_cost

    return grouped_expert_call_cost(
        hidden=m["hidden_size"], width=m["moe_intermediate_size"],
        held=m["num_experts"], total=m["num_experts"], up_stacks=2,
        n_out=n_out, pairs=pairs, here_share=here_share)


def expert_ffn_op(m: dict):
    """A predicate on a device operation's HLO text: true for the ROUTED
    feed-forward's operations (router and experts), told from the rest
    of a program by the expert axis in a shape they read or write. The
    dense layers' are matmuls of another width and count as none. For
    ``expert_ffn_share`` and ``prefill_expert_share``."""
    e, d, f = m["num_experts"], m["hidden_size"], m["moe_intermediate_size"]
    shapes = re.compile(
        r"\[(?:\d+,)*(?:"
        rf"{e},{d},{f}|{e},{f},{d}"        # the experts' weights
        rf"|{d},{e}"                       # the router
        rf"|\d+,{e},{f}|{e},\d+,{f}"       # [T, H, F], [H, T, F]
        r")\]")
    return lambda text: ("ragged-dot" in text
                         or shapes.search(text) is not None)
