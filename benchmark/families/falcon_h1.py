"""Hybrid decoders with a Mamba-2 mixer beside attention in every block,
served by ``ray_tpu.models.falcon_h1`` (tiiuae Falcon-H1, ``model_type``
``falcon_h1``): the adapter from the published Hugging Face keys to the
program's config class, the plain reference of the block, and its byte
counts (``benchmark/families/__init__.py`` says what a family is).

The reference follows the published implementation
(``transformers/models/falcon_h1/modeling_falcon_h1.py``; a CPU test
holds it to ``FalconH1ForCausalLM`` on copied weights), every multiplier
where that code applies it. With ``u = rms(h, input_layernorm)``:

    h <- h + ssm_out_multiplier * Mixer(u)
           + attention_out_multiplier * Attn(attention_in_multiplier * u)
    h <- h + MLP(rms(h, pre_ff_layernorm))

- ``Attn``: q, k, v without bias, the keys times ``key_multiplier``,
  rotary over the whole head (``rope_theta``, rotate-half), causal
  softmax of ``q k^T / sqrt(head_dim)``, each KV head serving its group
  of query heads, ``wo``.
- ``MLP(m) = down(up(m) * silu(gate(m) * mlp_multipliers[0]))
  * mlp_multipliers[1]``.
- ``Mixer(u)``: ``z | xBC | dt = in_proj(u * ssm_in_multiplier) * mup``
  (``ssm_multipliers`` over z, x, B, C and dt's columns);
  ``xBC <- silu(conv(xBC) + bias)``, a causal depthwise convolution of
  ``mamba_d_conv`` (zeros before the sequence's start); x in
  ``mamba_n_heads`` heads of ``mamba_d_head``, B and C in
  ``mamba_n_groups`` groups of ``mamba_d_state``, head j using group
  ``j // (heads / groups)``; ``dt <- softplus(dt + dt_bias)``,
  ``A = -exp(A_log)``; per head, from a zero state,
  ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``, ``y_t = S_t C_t +
  D x_t``: computed here TOKEN BY TOKEN, one ``lax.scan`` step a token
  (the program scans in chunks of 128); ``y * silu(z)`` RMS-normalised
  in ``mamba_n_groups`` groups (``mamba_norm_before_gate`` false: gate
  first, norm second); ``out_proj``.
- ``logits = rms(h, final_layernorm) @ lm_head * lm_head_multiplier``,
  ``h0 = embedding[tokens] * embedding_multiplier``.

NAMED DEPARTURES of ``logits``, each of which alone must read not
correct (the CPU tests): ``multipliers="none"`` (every multiplier one),
``gate_norm="before"`` (norm first, gate second), ``groups=1`` (the
gated norm over the whole width at once), ``conv_bias=False``,
``key_multiplier=1``.

It reads the program's parameter layout, which is data, not code (the
blocks stacked on a leading layer axis; ``wqkv`` holds the columns q | k
| v; ``in_proj`` z | x | B | C | dt), and imports nothing from the
program. One block's weights are converted to float32 at a time, a piece
of the block at a time, and the head's 1.34B parameters in blocks of the
vocabulary, so that 5.3 GB of float32 never stand beside the engine's
live arrays. On a TPU a float32 matrix multiplication runs in lower
precision unless told otherwise: ``logits`` runs under
``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp

HEAD_BLOCK = 32768          # columns of the head converted at once


# -- the adapter: the one part that touches the program -----------------

def model_config(config: dict):
    from ray_tpu.models import falcon_h1

    if not (config["mamba_rms_norm"] and not config["mamba_norm_before_gate"]
            and config["mamba_conv_bias"] and not config["mamba_proj_bias"]
            and not config["attention_bias"] and not config["mlp_bias"]
            and not config["projectors_bias"]
            and config["rope_scaling"] is None):
        raise ValueError("the program states the published Falcon-H1 block "
                         "only: gated grouped RMS norm after the gate, a "
                         "convolution bias and no other, plain rotary")
    return falcon_h1.FalconH1Config(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], d_ff=config["intermediate_size"],
        rope_theta=float(config["rope_theta"]),
        rms_eps=float(config["rms_norm_eps"]),
        d_ssm=config["mamba_d_ssm"], ssm_heads=config["mamba_n_heads"],
        ssm_head_dim=config["mamba_d_head"],
        ssm_state=config["mamba_d_state"],
        ssm_groups=config["mamba_n_groups"],
        ssm_conv=config["mamba_d_conv"],
        ssm_chunk=config["mamba_chunk_size"],
        embedding_multiplier=float(config["embedding_multiplier"]),
        lm_head_multiplier=float(config["lm_head_multiplier"]),
        attention_in_multiplier=float(config["attention_in_multiplier"]),
        attention_out_multiplier=float(config["attention_out_multiplier"]),
        ssm_in_multiplier=float(config["ssm_in_multiplier"]),
        ssm_out_multiplier=float(config["ssm_out_multiplier"]),
        key_multiplier=float(config["key_multiplier"]),
        mlp_multipliers=tuple(map(float, config["mlp_multipliers"])),
        ssm_multipliers=tuple(map(float, config["ssm_multipliers"])),
        dtype={"bfloat16": "bfloat16", "float32": "float32"}[
            config.get("torch_dtype", "bfloat16")],
        tie_embeddings=config["tie_word_embeddings"])


def init_params(model_cfg, key):
    from ray_tpu.models import falcon_h1

    return falcon_h1.init_params(model_cfg, key)


# -- the plain reference -------------------------------------------------

def _rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rope(x, positions, theta):
    """x [b, s, h, hd]; rotate pairs (i, i + hd/2)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions[..., None].astype(jnp.float32) * inv
    sin, cos = jnp.sin(ang)[:, :, None, :], jnp.cos(ang)[:, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _f32(p: dict, *names):
    return (p[name].astype(jnp.float32) for name in names)


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "head_dim", "theta", "eps", "in_m", "key_m",
    "out_m"))
def _attention(x, p, *, heads, kv_heads, head_dim, theta, eps, in_m, key_m,
               out_m):
    """The attention branch's term for the stream, from x [b, s, d]."""
    norm, wqkv, wo = _f32(p, "attn_norm", "wqkv", "wo")
    b, s, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    qkv = (_rms_norm(x, norm, eps) * in_m) @ wqkv
    qdim, kvdim = heads * head_dim, kv_heads * head_dim
    q = qkv[..., :qdim].reshape(b, s, heads, head_dim)
    k = qkv[..., qdim:qdim + kvdim].reshape(b, s, kv_heads, head_dim) * key_m
    v = qkv[..., qdim + kvdim:].reshape(b, s, kv_heads, head_dim)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    rep = heads // kv_heads
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    att = jnp.einsum("bqhd,bkhd->bhqk", q, k) * head_dim ** -0.5
    att = jnp.where(jnp.tril(jnp.ones((s, s), bool)), att, -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(att, -1), v)
    return (out.reshape(b, s, qdim) @ wo) * out_m


@functools.partial(jax.jit, static_argnames=(
    "di", "heads", "state", "groups", "eps", "in_m", "mup", "out_m",
    "gate_norm", "norm_groups", "conv_bias"))
def _mixer(x, p, *, di, heads, state, groups, eps, in_m, mup, out_m,
           gate_norm, norm_groups, conv_bias):
    """The mixer's term for the stream, from x [b, s, d]: the recurrence
    one token at a time from a zero state."""
    (norm, in_proj, conv_w, conv_b, dt_bias, a_log, d_skip, ssm_norm,
     out_proj) = _f32(p, "attn_norm", "in_proj", "conv_w", "conv_b",
                      "dt_bias", "A_log", "D", "ssm_norm", "out_proj")
    b, s, _ = x.shape
    gn = groups * state
    width = di // heads
    proj = (_rms_norm(x, norm, eps) * in_m) @ in_proj
    proj = proj * jnp.concatenate([
        jnp.full((w,), m, jnp.float32)
        for w, m in zip((di, di, gn, gn, heads), mup)])
    z, xbc, dt = proj[..., :di], proj[..., di:2 * di + 2 * gn], \
        proj[..., 2 * di + 2 * gn:]
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
    return (y @ out_proj) * out_m


@functools.partial(jax.jit, static_argnames=("eps", "gate_m", "down_m"))
def _mlp(x, p, *, eps, gate_m, down_m):
    norm, gate, up, down = _f32(p, "mlp_norm", "w_gate", "w_up", "w_down")
    h = _rms_norm(x, norm, eps)
    return x + ((h @ up) * jax.nn.silu((h @ gate) * gate_m)) @ down * down_m


@functools.partial(jax.jit, static_argnames=("eps",))
def _final_norm(x, w, *, eps):
    return _rms_norm(x, w.astype(jnp.float32), eps)


@jax.jit
def _head_block(x, w):
    return x @ w.astype(jnp.float32)


@jax.jit
def _embed(embedding, tokens):
    return embedding[tokens].astype(jnp.float32)


def logits(config: dict, params: dict, tokens, *, multipliers="published",
           gate_norm="after", groups=None, conv_bias=True,
           key_multiplier=None) -> jax.Array:
    """Float32 logits [b, s, vocab] of ``tokens`` [b, s], one block at a
    time. The keyword arguments are the named departures of the module
    docstring; their defaults are the published reading."""
    m = {k: config[k] for k in (
        "embedding_multiplier", "lm_head_multiplier",
        "attention_in_multiplier", "attention_out_multiplier",
        "ssm_in_multiplier", "ssm_out_multiplier", "key_multiplier")}
    mlp_m, ssm_m = tuple(config["mlp_multipliers"]), \
        tuple(config["ssm_multipliers"])
    if multipliers == "none":
        m = dict.fromkeys(m, 1.0)
        mlp_m, ssm_m = (1.0, 1.0), (1.0,) * 5
    if key_multiplier is not None:
        m["key_multiplier"] = key_multiplier
    eps = float(config["rms_norm_eps"])
    attn_kw = dict(
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        theta=float(config["rope_theta"]), eps=eps,
        in_m=float(m["attention_in_multiplier"]),
        key_m=float(m["key_multiplier"]),
        out_m=float(m["attention_out_multiplier"]))
    mixer_kw = dict(
        di=config["mamba_d_ssm"], heads=config["mamba_n_heads"],
        state=config["mamba_d_state"], groups=config["mamba_n_groups"],
        eps=eps, in_m=float(m["ssm_in_multiplier"]),
        mup=tuple(map(float, ssm_m)), out_m=float(m["ssm_out_multiplier"]),
        gate_norm=gate_norm, conv_bias=conv_bias,
        norm_groups=config["mamba_n_groups"] if groups is None else groups)
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embedding"], tokens) * float(
            m["embedding_multiplier"])
        for i in range(config["num_hidden_layers"]):
            p = jax.tree.map(lambda a: a[i], params["blocks"])
            x = x + _mixer(x, p, **mixer_kw) + _attention(x, p, **attn_kw)
            x = _mlp(x, p, eps=eps, gate_m=float(mlp_m[0]),
                     down_m=float(mlp_m[1]))
        x = _final_norm(x, params["final_norm"], eps=eps)
        head = (params["embedding"].T if config["tie_word_embeddings"]
                else params["lm_head"])
        out = [_head_block(x, head[:, i:i + HEAD_BLOCK])
               for i in range(0, head.shape[1], HEAD_BLOCK)]
        return jnp.concatenate(out, axis=-1) * float(m["lm_head_multiplier"])


# -- the counts ----------------------------------------------------------

def conv_dim(m: dict) -> int:
    return m["mamba_d_ssm"] + 2 * m["mamba_n_groups"] * m["mamba_d_state"]


def attention_params(m: dict) -> int:
    d, hd = m["hidden_size"], m["head_dim"]
    q, kv = m["num_attention_heads"] * hd, m["num_key_value_heads"] * hd
    return d * (q + 2 * kv) + q * d


def mixer_params(m: dict) -> int:
    """in_proj, out_proj, the convolution and its bias, dt_bias, A_log,
    D and the gated norm's vector."""
    d, di, c, h = (m["hidden_size"], m["mamba_d_ssm"], conv_dim(m),
                   m["mamba_n_heads"])
    return (d * (di + c + h) + di * d + c * m["mamba_d_conv"] + c + 3 * h
            + di)


def mlp_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def block_params(m: dict) -> int:
    return (attention_params(m) + mixer_params(m) + mlp_params(m)
            + 2 * m["hidden_size"])


def total_params(m: dict) -> int:
    d, v = m["hidden_size"], m["vocab_size"]
    head = 0 if m["tie_word_embeddings"] else d * v
    return m["num_hidden_layers"] * block_params(m) + d * v + head + d


def state_bytes_per_slot_layer(m: dict) -> int:
    """What one sequence keeps in one layer beside its KV pages: the
    float32 state [heads, head width, state size] and the bf16 tail of
    the convolution [taps - 1, conv width] (the configuration's
    ``assumed.state_dtype``)."""
    return (4 * m["mamba_d_ssm"] * m["mamba_d_state"]
            + 2 * (m["mamba_d_conv"] - 1) * conv_dim(m))


def kv_bytes_per_token_layer(m: dict) -> int:
    """Keys and values of one token in one layer, bf16."""
    return 2 * 2 * m["num_key_value_heads"] * m["head_dim"]


def _live_slots(counters: dict) -> float:
    samples = counters.get("occupancy_samples") or [0]
    return sum(samples) / len(samples)


def attention_kv_bytes(m: dict, counters: dict) -> float:
    """Bytes of keys and values one decode step must read: every live
    token's (the counter ``live_kv_tokens_mean``) in every layer."""
    return (kv_bytes_per_token_layer(m) * m["num_hidden_layers"]
            * counters.get("live_kv_tokens_mean", 0.0))


def ssm_state_bytes(m: dict, counters: dict) -> float:
    """Bytes of recurrent state one decode step must move: every live
    slot's state and tail (the mean number of live slots:
    ``occupancy_samples``) read once and written once in every layer."""
    return (2.0 * state_bytes_per_slot_layer(m) * m["num_hidden_layers"]
            * _live_slots(counters))


def decode_step_bytes(m: dict, counters: dict) -> float:
    """HBM bytes one decode step must move: the blocks' and the head's
    weights once (bf16; the embedding rows read are negligible), the
    live keys and values once, the live slots' recurrent state read and
    written once."""
    weights = 2.0 * (m["num_hidden_layers"] * block_params(m)
                     + m["hidden_size"] * m["vocab_size"])
    return (weights + attention_kv_bytes(m, counters)
            + ssm_state_bytes(m, counters))


def train_flops_per_token(m: dict, seq: int):
    """No training path for this family: at 16 bytes a parameter four of
    its blocks do not fit a chip (PERF.md, section 4)."""
    return None


def flash_train_cost(m: dict, batch: int, seq: int):
    return None


def ssm_op(m: dict):
    """Predicates on a device operation's HLO text, by the shapes the
    mixer alone has: ``state`` for the operations that read or write the
    recurrent state (the axes [.., heads, head width, state size]) or
    the convolution's tail ([.., taps - 1, conv width]), ``mixer`` for
    those and the mixer's projections (the input projection's width, the
    widths z | xBC | dt split into, the output projection's stack). For
    ``ssm_mixer_share.*``, ``ssm_state_roofline.*`` and
    ``prefill_scan_share.*``."""
    h, p, n = m["mamba_n_heads"], m["mamba_d_head"], m["mamba_d_state"]
    di, c, d = m["mamba_d_ssm"], conv_dim(m), m["hidden_size"]
    taps = m["mamba_d_conv"]
    state = re.compile(
        r"\[(?:\d+,)*(?:" rf"{h},{p},{n}|{taps - 1},{c}" r")\]")
    # z and y at the mixer's own width, where no other layer has it
    own_width = rf"|\d+,{di}" if di != d else ""
    mixer = re.compile(
        r"\[(?:\d+,)*(?:"
        rf"{di + c + h}"                     # the input projection
        rf"|{d},{di + c + h}|{di},{d}"       # its stack, and out_proj's
        rf"|\d+,{h},{p}|\d+,{h},\d+,{p}"     # x and y in heads
        rf"|{h},(\d+),\1"                    # a chunk's decays [H, q, q]
        rf"|{c},{taps}"                      # the convolution's filter
        rf"{own_width}"
        r")\]")

    def is_state(text: str) -> bool:
        return state.search(text) is not None

    def is_mixer(text: str) -> bool:
        return is_state(text) or mixer.search(text) is not None

    return {"state": is_state, "mixer": is_mixer}
