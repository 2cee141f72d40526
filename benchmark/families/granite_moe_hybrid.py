"""Hybrid decoders whose every layer is a Mamba-2 mixer or attention AND
THEN routed experts beside a shared one, served by
``ray_tpu.models.granite_moe_hybrid`` (IBM Granite 4.0-H, ``model_type``
``granitemoehybrid``): the adapter from the published Hugging Face keys
to the program's config class, the plain reference of the layer, and its
byte counts (``benchmark/families/__init__.py`` says what a family is).

The reference follows the published ``config.json``. With ``h`` the
stream, ``m`` = ``residual_multiplier``, RMSNorm in float32 with
``rms_norm_eps``, no bias anywhere but the convolution's:

- in: ``h = embedding[ids] * embedding_multiplier``;
- every layer i: ``h <- h + m * Mix_i(rms(h, norm_i))``, then ``h <- h +
  m * (Routed_i(g) + Shared_i(g))`` with ``g = rms(h, ffn_norm_i)``;
- ``Mix_i``, ``layer_types[i] == "mamba"`` (Mamba-2; ``mamba_n_heads`` H
  of ``mamba_d_head`` P, ``mamba_d_state`` N, ``mamba_n_groups`` G,
  ``mamba_d_conv`` K): ``z | xBC | dt = u W_in``; ``xBC <- silu(conv(xBC)
  + bias)``, a causal depthwise convolution (zeros before the sequence's
  start), split into x [H, P], B and C [G, N], head j using group ``j //
  (H / G)`` (one group: every head the same B and C); ``dt <- softplus(dt
  + dt_bias)``, ``A = -exp(A_log)``; per head, from a zero state, ``S_t =
  exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``, ``y_t = S_t C_t + D x_t``:
  computed here TOKEN BY TOKEN, one ``lax.scan`` step a token (the
  program scans in chunks); ``y * silu(z)`` RMS-normalised in G groups
  (gate first, norm second) times its weight; ``W_out``;
- ``Mix_i``, ``"attention"``: ``num_attention_heads`` query heads on
  ``num_key_value_heads`` KV heads of ``hidden_size /
  num_attention_heads``, NO rotary embedding (``position_embedding_type``
  ``nope``), causal softmax of ``q k^T * attention_multiplier``, ``W_o``;
- ``Routed_i``: ``logits = g W_r`` over all the router's experts; the
  ``num_experts_per_tok`` largest; their weights a softmax over those
  logits alone; expert e is ``(silu(g W_gate,e) * (g W_up,e)) W_down,e``
  (the published ``input_linear`` holds gate then up in one matrix; the
  program keeps two stacks, its layout is data);
- ``Shared_i``: the same form at ``shared_intermediate_size``, on every
  token;
- out: ``logits = rms(h, final_norm) embedding^T / logits_scaling`` (the
  head is tied).

What ``config.json`` leaves to the family's convention, or names and
nothing reads, is a NAMED DEPARTURE of ``logits``, with the reading taken
as its default, so that the other reading is one argument away (the
configuration file lists each under ``assumed``); each alone must read
not correct (the CPU tests, and ``scripts/check_seeds.py`` on the chip):

- ``attention_scale="multiplier"``: ``attention_multiplier``;
  ``"rsqrt_head_dim"`` scales the scores by ``head_dim ** -0.5``;
- ``residual=None``: ``residual_multiplier``; ``residual=1.0`` adds the
  branches whole;
- ``gating="softmax_topk"``; ``"softmax_all"`` weighs by a softmax over
  ALL the router's logits, not renormalised over the chosen;
- ``experts="swiglu"`` (``hidden_act`` silu); ``"reglu"`` gates by a
  ReLU, routed and shared experts alike;
- ``gate_norm="after"`` (gate first, norm second); ``"before"`` norms
  first;
- ``groups=None``: the gated norm in ``mamba_n_groups`` groups (one:
  over the whole inner width); ``groups=8`` in eight;
- ``rotary="none"``; ``"rope"`` rotates q and k over the whole head at
  ``rope_theta`` (rotate-half), which stands in the config and nothing
  reads;
- ``embedding=None``: ``embedding_multiplier``; ``embedding=1.0`` leaves
  it out.

``logits_scaling`` has no departure: dividing every logit of a row by
one number moves no greedy choice, and the comparison that decides
``correct`` teacher-forces greedy tokens, so a run cannot see it (nor
its absence: the limit is in the reference's own logits, which carry
it). It is held by the CPU test that compares the program's ``forward``
logits with these.

THE SHARE. ``num_local_experts`` in a configuration file is the
published number, the experts the router scores; of them
``expert_share.num_experts_held`` are HELD here (all of them, without the
key), starting at ``expert_share.index x num_experts_held``: the reference
routes over all of them and adds the held experts' part, as the program
does and as one chip of an expert-parallel pair would before the
exchange. (The other families' files put the held count under the
published key and list that key in ``reduced``; the accepted benchmark's
own test of every configuration, ``tests/bench/bench_pins.py:CUTS``, does
not know this family's key as a cut, and a ``model_config`` PR may not
edit it.) ``vocab_size`` is the slice held here.

It reads the program's parameter layout, which is data, not code
(``params["blocks"]`` maps ``layers<first>[-<last>]`` to that run of
identical layers' weights stacked on a leading axis; ``in_proj`` holds
the columns z | x | B | C | dt, ``wqkv`` q | k | v, ``wi_gate`` and
``wi_up`` the two halves of ``input_linear``), and imports nothing from
the program. One layer's weights are converted to float32 at a time, the
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


# -- the adapter: the one part that touches the program -----------------

def _share(config: dict) -> tuple:
    """(experts the router scores, experts held here, index of the first
    one held here)."""
    total = config["num_local_experts"]
    share = config.get("expert_share")
    if share is None:
        return total, total, 0
    held = share["num_experts_held"]
    return total, held, share["index"] * held


def _head_dim(config: dict) -> int:
    return config["hidden_size"] // config["num_attention_heads"]


def model_config(config: dict):
    from ray_tpu.models import granite_moe_hybrid

    if not (config["hidden_act"] == "silu"
            and config["normalization_function"] == "rmsnorm"
            and config["position_embedding_type"] == "nope"
            and config["mamba_conv_bias"] and not config["mamba_proj_bias"]
            and not config["attention_bias"]
            and len(config["layer_types"]) == config["num_hidden_layers"]):
        raise ValueError(
            "the program states the published Granite 4.0-H layers only: "
            "SwiGLU experts, RMS norms, no position embedding, a "
            "convolution bias and no other, an entry of layer_types a "
            "layer")
    total, held, first = _share(config)
    return granite_moe_hybrid.GraniteMoeHybridConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        layer_types=tuple(config["layer_types"]),
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"], head_dim=_head_dim(config),
        d_ssm=config["mamba_n_heads"] * config["mamba_d_head"],
        ssm_heads=config["mamba_n_heads"],
        ssm_head_dim=config["mamba_d_head"],
        ssm_state=config["mamba_d_state"],
        ssm_groups=config["mamba_n_groups"], ssm_conv=config["mamba_d_conv"],
        d_expert=config["intermediate_size"],
        d_shared=config["shared_intermediate_size"],
        n_experts=total, n_experts_held=held,
        first_expert=first, top_k=config["num_experts_per_tok"],
        embedding_multiplier=float(config["embedding_multiplier"]),
        attention_multiplier=float(config["attention_multiplier"]),
        residual_multiplier=float(config["residual_multiplier"]),
        logits_scaling=float(config["logits_scaling"]),
        rms_eps=float(config["rms_norm_eps"]),
        dtype={"bfloat16": "bfloat16", "float32": "float32"}[
            config.get("torch_dtype", "bfloat16")],
        tie_embeddings=config["tie_word_embeddings"])


def init_params(model_cfg, key):
    from ray_tpu.models import granite_moe_hybrid

    return granite_moe_hybrid.init_params(model_cfg, key)


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
    "heads", "kv_heads", "head_dim", "eps", "scale", "theta"))
def _attention(x, p, *, heads, kv_heads, head_dim, eps, scale, theta):
    """An ``attention`` layer's mixer, from x [b, s, d]. ``scale``: what
    the scores are multiplied by. ``theta``: None, or the base of the
    departure's rotary embedding."""
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
    att = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    att = jnp.where(jnp.tril(jnp.ones((s, s), bool)), att, -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(att, -1), v)
    return out.reshape(b, s, qdim) @ wo


@functools.partial(jax.jit, static_argnames=(
    "heads", "width", "state", "groups", "eps", "gate_norm", "norm_groups"))
def _mixer(x, p, *, heads, width, state, groups, eps, gate_norm,
           norm_groups):
    """A ``mamba`` layer's mixer, from x [b, s, d]: the recurrence one
    token at a time from a zero state."""
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
    xbc = jax.nn.silu(conv + conv_b)
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


def _gate(a, experts: str):
    return jax.nn.silu(a) if experts == "swiglu" else jax.nn.relu(a)


@functools.partial(jax.jit, static_argnames=(
    "eps", "top_k", "gating", "experts", "first"))
def _experts(x, p, *, eps, top_k, gating, experts, first):
    """A layer's second sublayer before the multiplier, from x [b, s, d]:
    the router scores every expert; experts ``first`` onwards, as many as
    the stacks hold, add their part, one expert at a time; the shared
    expert on every token."""
    norm, router, ws_gate, ws_up, ws_down = _f32(
        p, "ffn_norm", "router", "ws_gate", "ws_up", "ws_down")
    h = _rms_norm(x, norm, eps)
    logits = h @ router
    kth = jnp.sort(logits, axis=-1)[..., -top_k]
    chosen = logits >= kth[..., None]                        # [b, s, E]
    if gating == "softmax_topk":
        weight = jax.nn.softmax(jnp.where(chosen, logits, -jnp.inf), -1)
    else:
        weight = jnp.where(chosen, jax.nn.softmax(logits, axis=-1), 0.0)
    held = p["wi_up"].shape[0]
    weight, chosen = (a[..., first:first + held] for a in (weight, chosen))

    def one_expert(y, expert):
        gate, up, down, w, on = expert
        out = (_gate(h @ gate.astype(jnp.float32), experts)
               * (h @ up.astype(jnp.float32))) @ down.astype(jnp.float32)
        return y + jnp.where(on[..., None], w[..., None] * out, 0.0), None

    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(h),
        (p["wi_gate"], p["wi_up"], p["wo_e"], jnp.moveaxis(weight, -1, 0),
         jnp.moveaxis(chosen, -1, 0)))
    return routed + (_gate(h @ ws_gate, experts) * (h @ ws_up)) @ ws_down


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


def logits(config: dict, params: dict, tokens, *,
           attention_scale="multiplier", residual=None,
           gating="softmax_topk", experts="swiglu", gate_norm="after",
           groups=None, rotary="none", embedding=None) -> jax.Array:
    """Float32 logits [b, s, vocab] of ``tokens`` [b, s], one layer at a
    time. The keyword arguments are the named departures of the module
    docstring; their defaults are the configuration's reading."""
    eps = float(config["rms_norm_eps"])
    hd = _head_dim(config)
    m = float(config["residual_multiplier"] if residual is None
              else residual)
    attn_kw = dict(
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head_dim=hd, eps=eps,
        scale=(float(config["attention_multiplier"])
               if attention_scale == "multiplier" else hd ** -0.5),
        theta=float(config["rope_theta"]) if rotary == "rope" else None)
    mixer_kw = dict(
        heads=config["mamba_n_heads"], width=config["mamba_d_head"],
        state=config["mamba_d_state"], groups=config["mamba_n_groups"],
        eps=eps, gate_norm=gate_norm,
        norm_groups=config["mamba_n_groups"] if groups is None else groups)
    expert_kw = dict(eps=eps, top_k=config["num_experts_per_tok"],
                     gating=gating, experts=experts,
                     first=_share(config)[2])
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embedding"], tokens) * float(
            config["embedding_multiplier"] if embedding is None
            else embedding)
        for kind, p in zip(config["layer_types"], _layers(params["blocks"]),
                           strict=True):
            mix = (_mixer(x, p, **mixer_kw) if kind == "mamba"
                   else _attention(x, p, **attn_kw))
            x = x + m * mix
            x = x + m * _experts(x, p, **expert_kw)
        head = (params["embedding"] if config["tie_word_embeddings"]
                else params["lm_head"].T)
        return (_head(x, params["final_norm"], head, eps=eps)
                / float(config["logits_scaling"]))


# -- the counts ----------------------------------------------------------

def layer_counts(m: dict) -> tuple:
    """(mixer layers, attention layers); every layer has the experts."""
    kinds = m["layer_types"]
    return kinds.count("mamba"), kinds.count("attention")


def conv_dim(m: dict) -> int:
    return (m["mamba_n_heads"] * m["mamba_d_head"]
            + 2 * m["mamba_n_groups"] * m["mamba_d_state"])


def mixer_params(m: dict) -> int:
    """in_proj, out_proj, the convolution and its bias, dt_bias, A_log,
    D, the gated norm's vector and the mixer's norm."""
    d, h, c = m["hidden_size"], m["mamba_n_heads"], conv_dim(m)
    di = h * m["mamba_d_head"]
    return (d * (di + c + h) + di * d + c * m["mamba_d_conv"] + c + 3 * h
            + di + d)


def attention_params(m: dict) -> int:
    d, hd = m["hidden_size"], _head_dim(m)
    q, kv = m["num_attention_heads"] * hd, m["num_key_value_heads"] * hd
    return d * (q + 2 * kv) + q * d + d


def expert_params(m: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * m["hidden_size"] * m["intermediate_size"]


def shared_expert_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["shared_intermediate_size"]


def router_params(m: dict) -> int:
    """The router, float32."""
    return m["hidden_size"] * _share(m)[0]


def total_params(m: dict) -> int:
    """Parameters HELD here: the held experts of each layer, the
    ``vocab_size`` rows of the embedding (and of the head, where it is
    not tied)."""
    d, v = m["hidden_size"], m["vocab_size"]
    mixers, attention = layer_counts(m)
    feeds = (_share(m)[1] * expert_params(m)
             + shared_expert_params(m) + router_params(m) + d)
    head = 0 if m["tie_word_embeddings"] else d * v
    return (mixers * mixer_params(m) + attention * attention_params(m)
            + (mixers + attention) * feeds + d * v + head + d)


def state_bytes_per_slot_layer(m: dict) -> int:
    """What one sequence keeps in one ``mamba`` layer: the float32 state
    [heads, head width, state size] and the bf16 tail of the convolution
    [taps - 1, conv width] (the configuration's ``assumed.state_dtype``)."""
    return (4 * m["mamba_n_heads"] * m["mamba_d_head"] * m["mamba_d_state"]
            + 2 * (m["mamba_d_conv"] - 1) * conv_dim(m))


def kv_bytes_per_token_layer(m: dict) -> int:
    """Keys and values of one token in one ``attention`` layer, bf16."""
    return 2 * 2 * m["num_key_value_heads"] * _head_dim(m)


def _live_slots(counters: dict) -> float:
    samples = counters.get("occupancy_samples") or [0]
    return sum(samples) / len(samples)


def attention_kv_bytes(m: dict, counters: dict) -> float:
    """Bytes of keys and values one decode step must read: every live
    token's (the counter ``live_kv_tokens_mean``) in every ``attention``
    layer."""
    return (kv_bytes_per_token_layer(m) * layer_counts(m)[1]
            * counters.get("live_kv_tokens_mean", 0.0))


def ssm_state_bytes(m: dict, counters: dict) -> float:
    """Bytes of recurrent state one decode step must move: every live
    slot's state and tail (the mean number of live slots:
    ``occupancy_samples``) read once and written once in every ``mamba``
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
    layers' and the shared experts' weights and the tied embedding's held
    rows (bf16) and the routers (float32) once; of the held experts'
    weights the share that the live tokens reach (at the mean number of
    live slots); the live keys and values once; the live slots' recurrent
    state read and written once. The engine reads every held expert
    whatever the routing, so against this count its share of the roofline
    reads low, never high."""
    mixers, attention = layer_counts(m)
    layers = mixers + attention
    # the head's matrix once: tied, it IS the embedding's held rows (the
    # rows a step's tokens look up are nothing beside it)
    always = (2.0 * (mixers * mixer_params(m)
                     + attention * attention_params(m)
                     + layers * shared_expert_params(m)
                     + m["hidden_size"] * m["vocab_size"])
              + 4.0 * layers * router_params(m))
    experts = (2.0 * layers * _share(m)[1] * expert_params(m)
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
    and the mixer's own projections and scan: the input projection (its
    width, z | xBC | dt), the convolution's width and its filter, x and y
    in heads [.., H, P], a chunk's decays [H, q, q]. An ``out_proj``
    matmul carries none of these in its text and is left out (its rows
    are as wide as z, which is nothing's but the mixer's here, but a
    width alone is no axis to tell by), so the mixer's share reads low by
    it, never high. For ``ssm_mixer_share``, ``ssm_state_roofline`` and
    ``prefill_scan_share``."""
    h, p, n = m["mamba_n_heads"], m["mamba_d_head"], m["mamba_d_state"]
    c, d, taps = conv_dim(m), m["hidden_size"], m["mamba_d_conv"]
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
    family's widths (gate and up: two stacks):
    ``families.grouped_expert_call_cost``. For
    ``grouped_expert_ffn_roofline``."""
    from benchmark.families import grouped_expert_call_cost

    return grouped_expert_call_cost(
        hidden=m["hidden_size"], width=m["intermediate_size"],
        held=_share(m)[1], total=_share(m)[0], up_stacks=2,
        n_out=n_out, pairs=pairs, here_share=here_share)


def expert_ffn_op(m: dict):
    """A predicate on a device operation's HLO text: true for the ROUTED
    feed-forward's operations (router and held experts), told from the
    rest of a program by the expert axis in a shape they read or write.
    The shared expert's are dense matmuls of another width and count as
    none. For ``expert_ffn_share`` and ``prefill_expert_share``."""
    total, e, _ = _share(m)
    d, f = m["hidden_size"], m["intermediate_size"]
    shapes = re.compile(
        r"\[(?:\d+,)*(?:"
        rf"{e},{d},{f}|{e},{f},{d}"        # the held experts' weights
        rf"|{d},{total}"                   # the router
        rf"|\d+,{e},{f}|{e},\d+,{f}"       # [T, H, F], [H, T, F]
        r")\]")
    return lambda text: ("ragged-dot" in text
                         or shapes.search(text) is not None)
