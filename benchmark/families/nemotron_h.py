"""Hybrid decoders whose every layer is ONE thing, a Mamba-2 mixer, routed
experts or attention, served by ``ray_tpu.models.nemotron_h`` (NVIDIA
Nemotron 3 Nano, ``model_type`` ``nemotron_h``): the adapter from the
published Hugging Face keys to the program's config class, the plain
reference of the three kinds of layer, and their byte counts
(``benchmark/families/__init__.py`` says what a family is).

The reference follows the published ``config.json``, letter by letter of
``hybrid_override_pattern``: ``h <- h + Mix_i(rms(h, norm_i))``, RMSNorm
in float32 with ``layer_norm_epsilon``; then a final RMSNorm and an
untied head. No projection has a bias.

- ``M``, a Mamba-2 mixer (``mamba_num_heads`` H of ``mamba_head_dim`` P,
  ``ssm_state_size`` N, ``n_groups`` G, ``conv_kernel`` K): ``z | xBC |
  dt = u W_in``; ``xBC <- silu(conv(xBC) + bias)``, a causal depthwise
  convolution (zeros before the sequence's start), split into x [H, P],
  B and C [G, N], head j using group ``j // (H / G)``; ``dt <-
  softplus(dt + dt_bias)``, ``A = -exp(A_log)``; per head, from a zero
  state, ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``, ``y_t = S_t
  C_t + D x_t``: computed here TOKEN BY TOKEN, one ``lax.scan`` step a
  token (the program scans in chunks of ``chunk_size``); ``y * silu(z)``
  RMS-normalised in G groups (gate first, norm second) times its weight;
  ``W_out``.
- ``*``, attention: ``num_attention_heads`` query heads on
  ``num_key_value_heads`` KV heads of ``head_dim``, causal softmax of
  ``q k^T / sqrt(head_dim)``, ``W_o``. NO rotary embedding.
- ``E``, routed experts: ``s = sigmoid(u W_r)`` over all the router's
  experts; the ``num_experts_per_tok`` of largest ``s +
  e_score_correction_bias`` (``n_group`` 1: no groups); weights the chosen
  ``s`` over their sum + 1e-20 (``norm_topk_prob``) times
  ``routed_scaling_factor``; ``out = sum_k w_k relu(u W_up,k)^2 W_down,k +
  relu(u W_s,up)^2 W_s,down``.

What ``config.json`` names and nothing reads, or leaves to the family's
convention, is a NAMED DEPARTURE of ``logits``, with the reading taken as
its default, so that the other reading is one argument away (the
configuration file lists each under ``assumed``); each alone must read
not correct (the CPU tests, and ``scripts/check_departures.py`` on the
chip):

- ``rotary="none"``: the published block applies no rotary embedding
  (``rope_theta`` and ``partial_rotary_factor`` stand in the config and
  nothing reads them); ``rotary="rope"`` rotates q and k over the whole
  head at ``rope_theta`` (rotate-half);
- ``scores="sigmoid"``; ``scores="softmax"`` scores by a softmax over the
  router's logits;
- ``bias="correction"``; ``bias="none"`` chooses by the score alone;
- ``experts="relu2"``; ``experts="swiglu"`` puts ``silu(a) * a`` where
  the squared ReLU stands (a gated unit whose gate is its own input: the
  experts have no third matrix);
- ``scale=None``: ``routed_scaling_factor``; ``scale=1`` leaves it out;
- ``gate_norm="after"`` (gate first, norm second); ``"before"`` norms
  first;
- ``groups=None``: the gated norm in ``n_groups`` groups; ``groups=1``
  over the whole width at once;
- ``conv_bias=True`` (``use_conv_bias``); ``False`` leaves it out.

THE SHARE. ``n_routed_experts`` in a configuration file is the number of
experts HELD here, of ``expert_share.num_experts_total`` that the router
scores, starting at ``expert_share.index x n_routed_experts``: the
reference routes over all of them and adds the held experts' part, as the
program does and as one chip of an expert-parallel pair would before the
exchange. ``vocab_size`` is likewise the slice held here.

It reads the program's parameter layout, which is data, not code
(``params["blocks"]`` maps ``layers<first>[-<last>]`` to that run of
identical layers' weights stacked on a leading axis; ``in_proj`` holds
the columns z | x | B | C | dt, ``wqkv`` q | k | v), and imports nothing
from the program. One layer's weights are converted to float32 at a
time, the experts one expert at a time, the head in column blocks. On a
TPU a float32 matrix multiplication runs in lower precision unless told
otherwise: ``logits`` runs under
``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp

HEAD_BLOCK = 8192           # columns of the head converted at once


# -- the adapter: the one part that touches the program -----------------

def _share(config: dict) -> tuple:
    """(experts the router scores, index of the first one held here)."""
    share = config.get("expert_share")
    if share is None:
        return config["n_routed_experts"], 0
    return (share["num_experts_total"],
            share["index"] * config["n_routed_experts"])


def model_config(config: dict):
    from ray_tpu.models import nemotron_h

    if not (config["mlp_hidden_act"] == "relu2"
            and config["mamba_hidden_act"] == "silu"
            and config["use_conv_bias"] and not config["use_bias"]
            and not config["mamba_proj_bias"] and not config["mlp_bias"]
            and not config["attention_bias"]
            and config["n_group"] == 1 and config["topk_group"] == 1
            and config["n_shared_experts"] == 1
            and config["sliding_window"] is None
            and len(config["hybrid_override_pattern"])
            == config["num_hidden_layers"]):
        raise ValueError(
            "the program states the published Nemotron-H layers only: "
            "squared-ReLU experts in one group beside one shared expert, "
            "a convolution bias and no other, attention over the whole "
            "context, a letter of the pattern a layer")
    total, first = _share(config)
    return nemotron_h.NemotronHConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        pattern=config["hybrid_override_pattern"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        d_ssm=config["mamba_num_heads"] * config["mamba_head_dim"],
        ssm_heads=config["mamba_num_heads"],
        ssm_head_dim=config["mamba_head_dim"],
        ssm_state=config["ssm_state_size"], ssm_groups=config["n_groups"],
        ssm_conv=config["conv_kernel"], ssm_chunk=config["chunk_size"],
        time_step_min=float(config["time_step_min"]),
        time_step_max=float(config["time_step_max"]),
        time_step_floor=float(config["time_step_floor"]),
        d_expert=config["moe_intermediate_size"],
        d_shared=config["moe_shared_expert_intermediate_size"],
        n_experts=total, n_experts_held=config["n_routed_experts"],
        first_expert=first, top_k=config["num_experts_per_tok"],
        norm_topk_prob=config["norm_topk_prob"],
        routed_scale=float(config["routed_scaling_factor"]),
        rms_eps=float(config["layer_norm_epsilon"]),
        dtype={"bfloat16": "bfloat16", "float32": "float32"}[
            config.get("torch_dtype", "bfloat16")],
        tie_embeddings=config["tie_word_embeddings"])


def init_params(model_cfg, key):
    from ray_tpu.models import nemotron_h

    return nemotron_h.init_params(model_cfg, key)


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


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "head_dim", "eps", "theta"))
def _attention(x, p, *, heads, kv_heads, head_dim, eps, theta):
    """A ``*`` layer's term for the stream, from x [b, s, d]. ``theta``:
    None, or the base of the departure's rotary embedding."""
    norm, wqkv, wo = _f32(p, "norm", "wqkv", "wo")
    b, s, _ = x.shape
    qkv = _rms_norm(x, norm, eps) @ wqkv
    qdim, kvdim = heads * head_dim, kv_heads * head_dim
    q = qkv[..., :qdim].reshape(b, s, heads, head_dim)
    k = qkv[..., qdim:qdim + kvdim].reshape(b, s, kv_heads, head_dim)
    v = qkv[..., qdim + kvdim:].reshape(b, s, kv_heads, head_dim)
    if theta is not None:
        q, k = _rope(q, theta), _rope(k, theta)
    rep = heads // kv_heads
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    att = jnp.einsum("bqhd,bkhd->bhqk", q, k) * head_dim ** -0.5
    att = jnp.where(jnp.tril(jnp.ones((s, s), bool)), att, -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(att, -1), v)
    return out.reshape(b, s, qdim) @ wo


@functools.partial(jax.jit, static_argnames=(
    "heads", "width", "state", "groups", "eps", "gate_norm", "norm_groups",
    "conv_bias"))
def _mixer(x, p, *, heads, width, state, groups, eps, gate_norm,
           norm_groups, conv_bias):
    """An ``M`` layer's term for the stream, from x [b, s, d]: the
    recurrence one token at a time from a zero state."""
    (norm, in_proj, conv_w, conv_b, dt_bias, a_log, d_skip, ssm_norm,
     out_proj) = _f32(p, "norm", "in_proj", "conv_w", "conv_b", "dt_bias",
                      "A_log", "D", "ssm_norm", "out_proj")
    b, s, _ = x.shape
    di, gn = heads * width, groups * state
    proj = _rms_norm(x, norm, eps) @ in_proj
    z, xbc, dt = (proj[..., :di], proj[..., di:2 * di + 2 * gn],
                  proj[..., 2 * di + 2 * gn:])
    taps = conv_w.shape[-1]
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = sum(padded[:, i:i + s] * conv_w[:, i] for i in range(taps))
    if conv_bias:
        conv = conv + conv_b
    xbc = jax.nn.silu(conv)
    xs = xbc[..., :di].reshape(b, s, heads, width)
    per_head = heads // groups
    bs = jnp.repeat(xbc[..., di:di + gn].reshape(b, s, groups, state),
                    per_head, axis=2)
    cs = jnp.repeat(xbc[..., di + gn:].reshape(b, s, groups, state),
                    per_head, axis=2)
    dt = jax.nn.softplus(dt + dt_bias)                        # [b, s, H]
    a = -jnp.exp(a_log)

    def token(S, inp):
        x_t, b_t, c_t, dt_t = inp           # [b, H, P], [b, H, N] x 2, [b, H]
        S = (jnp.exp(dt_t * a)[..., None, None] * S
             + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return S, jnp.einsum("bhpn,bhn->bhp", S, c_t)

    _, y = jax.lax.scan(
        token, jnp.zeros((b, heads, width, state), jnp.float32),
        tuple(jnp.moveaxis(v, 1, 0) for v in (xs, bs, cs, dt)))
    y = jnp.moveaxis(y, 0, 1) + d_skip[:, None] * xs
    y = y.reshape(b, s, di)

    def grouped_norm(v):
        g = v.reshape(b, s, norm_groups, di // norm_groups)
        g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), -1, keepdims=True)
                              + eps)
        return g.reshape(b, s, di) * ssm_norm

    if gate_norm == "after":                  # gate first, norm second
        y = grouped_norm(y * jax.nn.silu(z))
    else:
        y = grouped_norm(y) * jax.nn.silu(z)
    return y @ out_proj


def _act(a, experts: str):
    return jnp.square(jax.nn.relu(a)) if experts == "relu2" \
        else jax.nn.silu(a) * a


@functools.partial(jax.jit, static_argnames=(
    "eps", "top_k", "norm_topk_prob", "scale", "scores", "bias", "experts",
    "first"))
def _experts(x, p, *, eps, top_k, norm_topk_prob, scale, scores, bias,
             experts, first):
    """An ``E`` layer's term for the stream, from x [b, s, d]: the router
    scores every expert; experts ``first`` onwards, as many as the stacks
    hold, add their part, one expert at a time; the shared expert on
    every token."""
    norm, router, router_bias, ws_up, ws_down = _f32(
        p, "norm", "router", "router_bias", "ws_up", "ws_down")
    h = _rms_norm(x, norm, eps)
    logits = h @ router
    score = (jax.nn.sigmoid(logits) if scores == "sigmoid"
             else jax.nn.softmax(logits, axis=-1))
    choice = score + router_bias if bias == "correction" else score
    kth = jnp.sort(choice, axis=-1)[..., -top_k]
    chosen = choice >= kth[..., None]                        # [b, s, E]
    weight = jnp.where(chosen, score, 0.0)
    if norm_topk_prob:
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
    weight = weight * scale
    held = p["wi_up"].shape[0]
    weight, chosen = (a[..., first:first + held] for a in (weight, chosen))

    def one_expert(y, expert):
        up, down, w, on = expert
        out = _act(h @ up.astype(jnp.float32), experts) \
            @ down.astype(jnp.float32)
        return y + jnp.where(on[..., None], w[..., None] * out, 0.0), None

    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(h),
        (p["wi_up"], p["wo_e"], jnp.moveaxis(weight, -1, 0),
         jnp.moveaxis(chosen, -1, 0)))
    return routed + _act(h @ ws_up, experts) @ ws_down


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, lm_head, *, eps):
    x = _rms_norm(x, final_norm.astype(jnp.float32), eps)
    cols = lm_head.shape[1]
    return jnp.concatenate(
        [x @ lm_head[:, c:c + HEAD_BLOCK].astype(jnp.float32)
         for c in range(0, cols, HEAD_BLOCK)], axis=-1)


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


def logits(config: dict, params: dict, tokens, *, rotary="none",
           scores="sigmoid", bias="correction", experts="relu2", scale=None,
           gate_norm="after", groups=None, conv_bias=True) -> jax.Array:
    """Float32 logits [b, s, vocab] of ``tokens`` [b, s], one layer at a
    time. The keyword arguments are the named departures of the module
    docstring; their defaults are the configuration's reading."""
    eps = float(config["layer_norm_epsilon"])
    attn_kw = dict(
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        eps=eps,
        theta=float(config["rope_theta"]) if rotary == "rope" else None)
    mixer_kw = dict(
        heads=config["mamba_num_heads"], width=config["mamba_head_dim"],
        state=config["ssm_state_size"], groups=config["n_groups"], eps=eps,
        gate_norm=gate_norm, conv_bias=conv_bias,
        norm_groups=config["n_groups"] if groups is None else groups)
    expert_kw = dict(
        eps=eps, top_k=config["num_experts_per_tok"],
        norm_topk_prob=bool(config["norm_topk_prob"]),
        scale=float(config["routed_scaling_factor"]
                    if scale is None else scale),
        scores=scores, bias=bias, experts=experts, first=_share(config)[1])
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embedding"], tokens)
        for letter, p in zip(config["hybrid_override_pattern"],
                             _layers(params["blocks"]), strict=True):
            if letter == "M":
                x = x + _mixer(x, p, **mixer_kw)
            elif letter == "*":
                x = x + _attention(x, p, **attn_kw)
            else:
                x = x + _experts(x, p, **expert_kw)
        head = (params["embedding"].T if config["tie_word_embeddings"]
                else params["lm_head"])
        return _head(x, params["final_norm"], head, eps=eps)


# -- the counts ----------------------------------------------------------

def layer_counts(m: dict) -> tuple:
    """(mixer layers, expert layers, attention layers)."""
    pattern = m["hybrid_override_pattern"]
    return pattern.count("M"), pattern.count("E"), pattern.count("*")


def conv_dim(m: dict) -> int:
    return (m["mamba_num_heads"] * m["mamba_head_dim"]
            + 2 * m["n_groups"] * m["ssm_state_size"])


def mixer_params(m: dict) -> int:
    """in_proj, out_proj, the convolution and its bias, dt_bias, A_log,
    D, the gated norm's vector and the layer's norm."""
    d, h, c = m["hidden_size"], m["mamba_num_heads"], conv_dim(m)
    di = h * m["mamba_head_dim"]
    return (d * (di + c + h) + di * d + c * m["conv_kernel"] + c + 3 * h
            + di + d)


def attention_params(m: dict) -> int:
    d, hd = m["hidden_size"], m["head_dim"]
    q, kv = m["num_attention_heads"] * hd, m["num_key_value_heads"] * hd
    return d * (q + 2 * kv) + q * d + d


def expert_params(m: dict) -> int:
    """One routed expert: up and down."""
    return 2 * m["hidden_size"] * m["moe_intermediate_size"]


def shared_expert_params(m: dict) -> int:
    return 2 * m["hidden_size"] * m["moe_shared_expert_intermediate_size"]


def router_params(m: dict) -> int:
    """The router and its correction bias, both float32."""
    return (m["hidden_size"] + 1) * _share(m)[0]


def total_params(m: dict) -> int:
    """Parameters HELD here: ``n_routed_experts`` experts an ``E`` layer,
    the ``vocab_size`` rows of the embedding and columns of the head."""
    d, v = m["hidden_size"], m["vocab_size"]
    mixers, sparse, attention = layer_counts(m)
    layer = (m["n_routed_experts"] * expert_params(m)
             + shared_expert_params(m) + router_params(m) + d)
    head = 0 if m["tie_word_embeddings"] else d * v
    return (mixers * mixer_params(m) + attention * attention_params(m)
            + sparse * layer + d * v + head + d)


def state_bytes_per_slot_layer(m: dict) -> int:
    """What one sequence keeps in one ``M`` layer: the float32 state
    [heads, head width, state size] and the bf16 tail of the convolution
    [taps - 1, conv width] (the configuration's ``assumed.state_dtype``)."""
    return (4 * m["mamba_num_heads"] * m["mamba_head_dim"]
            * m["ssm_state_size"]
            + 2 * (m["conv_kernel"] - 1) * conv_dim(m))


def kv_bytes_per_token_layer(m: dict) -> int:
    """Keys and values of one token in one ``*`` layer, bf16."""
    return 2 * 2 * m["num_key_value_heads"] * m["head_dim"]


def _live_slots(counters: dict) -> float:
    samples = counters.get("occupancy_samples") or [0]
    return sum(samples) / len(samples)


def attention_kv_bytes(m: dict, counters: dict) -> float:
    """Bytes of keys and values one decode step must read: every live
    token's (the counter ``live_kv_tokens_mean``) in every ``*`` layer."""
    return (kv_bytes_per_token_layer(m) * layer_counts(m)[2]
            * counters.get("live_kv_tokens_mean", 0.0))


def ssm_state_bytes(m: dict, counters: dict) -> float:
    """Bytes of recurrent state one decode step must move: every live
    slot's state and tail (the mean number of live slots:
    ``occupancy_samples``) read once and written once in every ``M``
    layer."""
    return (2.0 * state_bytes_per_slot_layer(m) * layer_counts(m)[0]
            * _live_slots(counters))


def experts_touched_share(m: dict, live_tokens: float) -> float:
    """The share of the HELD experts that ``live_tokens`` tokens reach
    when each picks ``num_experts_per_tok`` of all the router's experts
    uniformly: 1 - (1 - k / E) ** n."""
    k, e = m["num_experts_per_tok"], _share(m)[0]
    return 1.0 - (1.0 - k / e) ** live_tokens


def decode_step_bytes(m: dict, counters: dict) -> float:
    """HBM bytes one decode step must move: the mixers', the attention
    layers', the shared experts' and the head's weights (bf16) and the
    routers (float32) once; of the held experts' weights the share that
    the live tokens reach (at the mean number of live slots); the live
    keys and values once; the live slots' recurrent state read and
    written once. The engine reads every held expert whatever the
    routing, so against this count its share of the roofline reads low,
    never high."""
    mixers, sparse, attention = layer_counts(m)
    always = (2.0 * (mixers * mixer_params(m)
                     + attention * attention_params(m)
                     + sparse * shared_expert_params(m)
                     + m["hidden_size"] * m["vocab_size"])
              + 4.0 * sparse * router_params(m))
    experts = (2.0 * sparse * m["n_routed_experts"] * expert_params(m)
               * experts_touched_share(m, _live_slots(counters)))
    return (always + experts + attention_kv_bytes(m, counters)
            + ssm_state_bytes(m, counters))


def train_flops_per_token(m: dict, seq: int):
    """No training path for this family (the trainer runs one block
    repeated; a share of the experts trains only with the exchange this
    cut leaves out)."""
    return None


def flash_train_cost(m: dict, batch: int, seq: int):
    return None


def ssm_op(m: dict):
    """Predicates on a device operation's HLO text, by the shapes the
    mixer ALONE has: ``state`` for the operations that read or write the
    recurrent state (the axes [.., heads, head width, state size]) or the
    convolution's tail ([.., taps - 1, conv width]); ``mixer`` for those
    and the mixer's own projections and scan. Shapes alone do not tell
    every operation of this model apart: ``out_proj`` and attention's
    ``wo`` are both [heads x width, hidden] and ``z``, ``y`` and ``q`` are
    all that wide, so neither a stack of that shape nor a row of that
    width counts. What does: the input projection (its width, z | xBC |
    dt), the convolution's width and its filter, x and y in heads
    [.., H, P], a chunk's decays [H, q, q]. An ``out_proj`` matmul that
    carries none of these in its text is left out, and the mixer's share
    reads low by it (a hundredth of a decode step's bytes at the
    published widths), never high by attention's. For
    ``ssm_mixer_share.*``, ``ssm_state_roofline.*`` and
    ``prefill_scan_share.*``."""
    h, p, n = m["mamba_num_heads"], m["mamba_head_dim"], m["ssm_state_size"]
    c, d, taps = conv_dim(m), m["hidden_size"], m["conv_kernel"]
    proj = h * p + c + h
    state = re.compile(
        r"\[(?:\d+,)*(?:" rf"{h},{p},{n}|{taps - 1},{c}" r")\]")
    mixer = re.compile(
        r"\[(?:\d+,)*(?:"
        rf"{proj}|{d},{proj}"                # the input projection
        rf"|\d+,{c}|{c},{taps}|{c}"          # xBC, the filter, its bias
        rf"|\d+,{h},{p}|\d+,{h},\d+,{p}"     # x and y in heads
        rf"|{h},(\d+),\1"                    # a chunk's decays [H, q, q]
        r")\]")

    def is_state(text: str) -> bool:
        return state.search(text) is not None

    def is_mixer(text: str) -> bool:
        return is_state(text) or mixer.search(text) is not None

    return {"state": is_state, "mixer": is_mixer}


def grouped_expert_cost(m: dict, n_out: int, pairs: float,
                        here_share=None):
    """What one call of the grouped expert kernel must do, at this
    family's widths (up alone: relu2 has no gate): ``families.grouped_expert_call_cost``.
    For ``grouped_expert_ffn_roofline``."""
    from benchmark.families import grouped_expert_call_cost

    return grouped_expert_call_cost(
        hidden=m["hidden_size"], width=m["moe_intermediate_size"], held=m["n_routed_experts"],
        total=_share(m)[0], up_stacks=1, n_out=n_out, pairs=pairs,
        here_share=here_share)


def expert_ffn_op(m: dict):
    """A predicate on a device operation's HLO text: true for the ROUTED
    feed-forward's operations (router and held experts), told from the
    rest of a program by the expert axis in a shape they read or write.
    The shared expert's are dense matmuls of another width and count as
    none. For ``expert_ffn_share.*`` and ``prefill_expert_share.*``."""
    e, total = m["n_routed_experts"], _share(m)[0]
    d, f = m["hidden_size"], m["moe_intermediate_size"]
    shapes = re.compile(
        r"\[(?:\d+,)*(?:"
        rf"{e},{d},{f}|{e},{f},{d}"        # the held experts' weights
        rf"|{d},{total}"                   # the router
        rf"|\d+,{e},{f}|{e},\d+,{f}"       # [T, H, F], [H, T, F]
        r")\]")
    return lambda text: ("ragged-dot" in text
                         or shapes.search(text) is not None)
