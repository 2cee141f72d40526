"""Falcon-H1-family hybrid decoder (tiiuae Falcon-H1, ``model_type``
``falcon_h1``), for serving.

Every block holds TWO sequence mixers side by side on one normed input
``u = rms(h)``: a Mamba-2 state-space mixer and grouped-query attention,
their outputs added to the stream together, then a SwiGLU feed-forward:

    h <- h + ssm_out * Mixer(u) + attn_out * Attn(attn_in * u)
    h <- h + MLP(rms(h))

with fixed scalar multipliers (muP) at the embedding, the head, both
mixers' inputs and outputs, the keys, the MLP's gate and output, and one
each for the five parts of the mixer's input projection.

The mixer (``mamba_d_ssm`` = ``H`` heads of ``P``, state ``N``, ``G``
groups, convolution of ``K``): ``z | xBC | dt = in_proj(u * ssm_in) *
mup``; ``xBC <- silu(causal depthwise conv(xBC) + bias)``, split into
``x`` [H, P], ``B`` and ``C`` [G, N]; ``dt <- softplus(dt + dt_bias)``,
``A = -exp(A_log)``; the recurrence ``S <- exp(dt A) S + dt x (x) B``,
``y = S C + D x`` (``ops/ssm.py``); ``y * silu(z)`` RMS-normalised in
``G`` groups (gate first, norm second); ``out_proj``.

So a sequence's state is KV pages AND, per layer, the recurrent state
``S`` [H, P, N] (float32) and the convolution's tail, the last ``K - 1``
``xBC`` rows [K-1, C]. ``layer_plan`` says so (``RecurrentState``), and
the block has a fourth piece beside ``attention_projections`` /
``attention_output`` / ``feed_forward``: the mixer, over a padded block
of tokens from a given state (``recurrent_mixer``, a prefill) and for
one token a slot (``recurrent_step``, a decode step, which advances the
states in the stacked arrays they are handed in). The pieces take no
view on where keys or values live: ``forward`` puts plain causal
attention and a zero starting state between them, the paged serving
engine its page pool and its slots' states (``serve/paged_llm.py``).

q, k and v are ONE stack ``wqkv`` (columns q | k | v), as Laguna's are
and for its reason: four layers' ``wk`` (21 MB) fit the core's memory,
and a stack parked there is moved round every attention kernel
(``models/llama.py: fuse_attention_projections``). No multiplier is
folded into a weight: each is applied where the published code applies
it, on activations, where it fuses into the elementwise work around it.
No training path: there are no logical axes and no loss here.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.llama import LayerStack, fanin_init, lm_head_weights
from ray_tpu.ops import scopes
from ray_tpu.ops.attention import cached_attention
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.rope import apply_rope, rope_sin_cos
from ray_tpu.ops.ssm import (causal_conv, last_rows, ssm_scan,
                             ssm_state_step)


@dataclass(frozen=True)
class FalconH1Config:
    """Falcon-H1-34B-Instruct as published (``config.json``)."""
    vocab_size: int = 261120
    d_model: int = 5120
    n_layers: int = 72
    n_heads: int = 20
    n_kv_heads: int = 4
    head_dim: int = 128
    d_ff: int = 21504
    rope_theta: float = 1e11
    rms_eps: float = 1e-5
    # the mixer
    d_ssm: int = 4096                 # mamba_d_ssm = ssm_heads x ssm_head_dim
    ssm_heads: int = 32
    ssm_head_dim: int = 128
    ssm_state: int = 256              # mamba_d_state
    ssm_groups: int = 2
    ssm_conv: int = 4                 # mamba_d_conv
    ssm_chunk: int = 128
    # the multipliers
    embedding_multiplier: float = 5.656854249492381
    lm_head_multiplier: float = 0.0078125
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    ssm_in_multiplier: float = 0.25
    ssm_out_multiplier: float = 0.08838834764831845
    key_multiplier: float = 0.011048543456039804
    mlp_multipliers: tuple = (0.1767766952966369, 0.011160714285714284)
    ssm_multipliers: tuple = (0.3535533905932738, 0.25, 0.1767766952966369,
                              0.5, 0.3535533905932738)
    dtype: str = "bfloat16"
    tie_embeddings: bool = False

    def __post_init__(self):
        if self.ssm_heads * self.ssm_head_dim != self.d_ssm:
            raise ValueError(
                f"{self.ssm_heads} mixer heads of {self.ssm_head_dim} are "
                f"not mamba_d_ssm = {self.d_ssm}")
        if self.ssm_heads % self.ssm_groups or self.d_ssm % self.ssm_groups:
            raise ValueError(f"{self.ssm_groups} groups do not divide "
                             f"{self.ssm_heads} heads")

    @property
    def conv_dim(self) -> int:
        """Width of ``xBC``: x, then B and C over the groups."""
        return self.d_ssm + 2 * self.ssm_groups * self.ssm_state

    @property
    def param_dtype(self):
        return jnp.dtype(self.dtype)


def falcon_h1_34b_instruct() -> FalconH1Config:
    return FalconH1Config()


def falcon_h1_tiny(vocab_size: int = 128, **changes) -> FalconH1Config:
    """Test-size config in float32: query groups of 5 and two mixer
    groups as published, a scan chunk of 8 so that a short prompt spans
    several chunks, every multiplier another number than one."""
    kw = dict(vocab_size=vocab_size, d_model=64, n_layers=2, n_heads=10,
              n_kv_heads=2, head_dim=16, d_ff=96, rope_theta=1e4,
              d_ssm=48, ssm_heads=6, ssm_head_dim=8, ssm_state=16,
              ssm_groups=2, ssm_conv=4, ssm_chunk=8,
              embedding_multiplier=2.5, lm_head_multiplier=0.25,
              attention_in_multiplier=1.5, attention_out_multiplier=0.6,
              ssm_in_multiplier=0.5, ssm_out_multiplier=0.8,
              key_multiplier=0.3, mlp_multipliers=(0.7, 0.4),
              ssm_multipliers=(0.9, 0.6, 0.7, 1.2, 0.8), dtype="float32")
    kw.update(changes)
    return FalconH1Config(**kw)


# ---------------------------------------------------------------------------
# The layer plan
# ---------------------------------------------------------------------------

class RecurrentState(NamedTuple):
    """What a run of layers with a recurrent mixer keeps per sequence and
    layer beside its KV pages, as ``LayerStack.state``: the arrays
    (name, shape, dtype) in the order the mixer takes and returns them,
    and the length of the chunks its scan cuts a prompt into.
    ``pages_keep``: whether the state is worth a page's keeping: every
    page then keeps, under its own id, the state at its END beside its K
    and V rows (one more store an array, ``serve/engine_programs.py``),
    a prefix hit of ``k`` pages hands the prefill the state page ``k -
    1`` keeps, and the plan's prefix is reusable. For a state of
    kilobytes (a gated short convolution's tail,
    ``models/lfm2_moe.py``: an eighth of a page's K and V); a Mamba-2
    state is as many bytes as sixteen pages of the same layers, and its
    plans leave this off. A module whose plan says so gives the states
    at the page ends: its ``recurrent_mixer`` takes ``page_ends=`` (a
    page's tokens) and returns them third, [n, pages, ...] an array."""
    arrays: tuple
    chunk: int
    pages_keep: bool = False


def layer_plan(cfg: FalconH1Config) -> tuple:
    """One run: every layer is the same block, stacked in
    ``params["blocks"]``, attends over its whole context, and holds a
    recurrent mixer whose per-sequence state the run states."""
    state = RecurrentState(
        (("ssm_state", (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
          "float32"),
         ("conv_tail", (cfg.ssm_conv - 1, cfg.conv_dim), cfg.dtype)),
        cfg.ssm_chunk)
    return (LayerStack(None, "full", None, cfg.n_layers, state),)


def rotary_tables(cfg: FalconH1Config, positions) -> dict:
    return {"full": rope_sin_cos(positions, cfg.head_dim,
                                 theta=cfg.rope_theta)}


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

# Seeded weights that the published multipliers leave a working network.
# The multipliers are muP's: in the trained model they stand against
# weights that are large where the multiplier is small. Random weights at
# the plain fan-in scale do not: the stream would be the embedding times
# 5.66 with branches a hundredth its size, the keys a hundredth of the
# queries (uniform attention), and the logits 0.0078 of unit variance, all
# of them within 0.03 of each other, so that ANY token would pass the
# reference check (``benchmark/serving.py``: the chosen token's logit
# within 0.1 of the reference's best). So each matrix is drawn at the
# fan-in scale OVER the multiplier that follows it: the embedding's rows,
# q, k, v, the mixer's z, x, B, C and dt, the MLP's gate and the three
# branches' outputs have unit variance after their multiplier, and the
# logits have unit variance over the vocabulary, as the other families'
# cells have. The multipliers are applied as published, in the program
# and in the reference alike. Attention over hundreds of random keys
# averages its values to a tenth of the other branches' size (Laguna's
# finding, ``models/laguna.py``), so ``wo`` is at four times that scale.
# The mixer's own parameters are Mamba-2's initialisation
# (``modeling_falcon_h1.py`` only carries placeholders: ``A_log =
# log(1..H)``, ``dt_bias`` one): ``A_log = log(uniform(1, 16))``,
# ``dt_bias`` the inverse softplus of a log-uniform step in [0.001, 0.1],
# ``D`` one, the filter at the fan-in scale of its 4 taps and its bias at
# 0.3 (a torch ``Conv1d`` of 4 taps draws both from U(-0.5, 0.5)); norm
# vectors one.
_DT_MIN, _DT_MAX = 0.001, 0.1
_ATTENTION_OUT_GAIN = 4.0


def mup_vector(cfg: FalconH1Config):
    """``ssm_multipliers`` over the columns of the mixer's input
    projection: z | x | B | C | dt (``compute_mup_vector``)."""
    gn = cfg.ssm_groups * cfg.ssm_state
    widths = (cfg.d_ssm, cfg.d_ssm, gn, gn, cfg.ssm_heads)
    return jnp.concatenate([jnp.full((w,), m, jnp.float32)
                            for w, m in zip(widths, cfg.ssm_multipliers)])


def init_params(cfg: FalconH1Config, key) -> dict:
    """The parameter pytree, blocks stacked on a leading layer axis."""
    dt = cfg.param_dtype
    d, l, di, c = cfg.d_model, cfg.n_layers, cfg.d_ssm, cfg.conv_dim
    qdim, kvdim = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    proj = di + c + cfg.ssm_heads

    def dense(key, shape, fan_in, over=1.0):
        """Fan-in scale over ``over`` (a scalar, or one a column)."""
        return (fanin_init(key, shape, fan_in) / over).astype(dt)

    k_emb, k_head, k_blocks = jax.random.split(key, 3)
    ks = jax.random.split(k_blocks, 11)
    step = jnp.exp(jax.random.uniform(
        ks[9], (l, cfg.ssm_heads), jnp.float32,
        math.log(_DT_MIN), math.log(_DT_MAX)))
    qkv_over = jnp.concatenate([
        jnp.ones((qdim,)), jnp.full((kvdim,), cfg.key_multiplier),
        jnp.ones((kvdim,))]) * cfg.attention_in_multiplier
    gate_m, down_m = cfg.mlp_multipliers
    blocks = {
        "attn_norm": jnp.ones((l, d), dtype=dt),
        "wqkv": dense(ks[0], (l, d, qdim + 2 * kvdim), d, qkv_over),
        "wo": dense(ks[1], (l, qdim, d), qdim,
                    cfg.attention_out_multiplier / _ATTENTION_OUT_GAIN),
        "in_proj": dense(ks[2], (l, d, proj), d,
                         cfg.ssm_in_multiplier * mup_vector(cfg)),
        "conv_w": dense(ks[3], (l, c, cfg.ssm_conv), cfg.ssm_conv),
        "conv_b": (0.3 * jax.random.normal(ks[4], (l, c), jnp.float32)
                   ).astype(dt),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),   # softplus^-1(step)
        "A_log": jnp.log(jax.random.uniform(
            ks[10], (l, cfg.ssm_heads), jnp.float32, 1.0, 16.0)),
        "D": jnp.ones((l, cfg.ssm_heads), jnp.float32),
        "ssm_norm": jnp.ones((l, di), dtype=dt),
        "out_proj": dense(ks[5], (l, di, d), di, cfg.ssm_out_multiplier),
        "mlp_norm": jnp.ones((l, d), dtype=dt),
        "w_gate": dense(ks[6], (l, d, cfg.d_ff), d, gate_m),
        "w_up": dense(ks[7], (l, d, cfg.d_ff), d),
        "w_down": dense(ks[8], (l, cfg.d_ff, d), cfg.d_ff, down_m),
    }
    params = {
        "embedding": dense(k_emb, (cfg.vocab_size, d), 1,
                           cfg.embedding_multiplier),
        "blocks": blocks,
        "final_norm": jnp.ones((d,), dtype=dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense(k_head, (d, cfg.vocab_size), d,
                                  cfg.lm_head_multiplier)
    return params


# ---------------------------------------------------------------------------
# The block, as four pieces
# ---------------------------------------------------------------------------

def embed(cfg: FalconH1Config, params, tokens):
    """Token ids -> the stream's start, ``embedding_multiplier`` applied
    (the engine's programs ask the module that has this piece for it)."""
    with jax.named_scope(scopes.EMBED):
        x = params["embedding"][tokens]
        return (x.astype(jnp.float32)
                * cfg.embedding_multiplier).astype(x.dtype)


def head_logits(cfg: FalconH1Config, params, x):
    """Normed last hidden states [b, d] -> float32 logits [b, vocab],
    ``lm_head_multiplier`` applied."""
    with jax.named_scope(scopes.LM_HEAD):
        return jnp.einsum("bd,dv->bv", x, lm_head_weights(cfg, params),
                          preferred_element_type=jnp.float32
                          ) * cfg.lm_head_multiplier


def attention_projections(cfg: FalconH1Config, p, x, sin, cos):
    """What attention takes in, from the residual stream ``x`` [b, s, d]:
    the block's one pre-norm times ``attention_in_multiplier``, q | k | v
    from the one stack, the keys times ``key_multiplier``, rotary over
    the whole head on q and k. Returns (q [b, s, heads, hd], k, v
    [b, s, kv heads, hd])."""
    b, s, _ = x.shape
    qdim, kvdim = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    with jax.named_scope(scopes.ATTN_QKV):
        u = rms_norm(x, p["attn_norm"], eps=cfg.rms_eps)
        u = (u.astype(jnp.float32)
             * cfg.attention_in_multiplier).astype(u.dtype)
        q, k, v = (y.reshape(b, s, -1, cfg.head_dim) for y in jnp.split(
            u @ p["wqkv"], [qdim, qdim + kvdim], axis=-1))
        k = (k.astype(jnp.float32) * cfg.key_multiplier).astype(k.dtype)
        return apply_rope(q, sin, cos), apply_rope(k, sin, cos), v


def attention_output(cfg: FalconH1Config, p, x, attn):
    """The attention branch's end: the heads' outputs through ``wo``,
    times ``attention_out_multiplier``, added to ``x`` [b, s, d]."""
    b, s, _ = x.shape
    with jax.named_scope(scopes.ATTN_OUT):
        out = jnp.einsum("bsq,qd->bsd", attn.reshape(b, s, -1), p["wo"],
                         preferred_element_type=jnp.float32)
        return x + (out * cfg.attention_out_multiplier).astype(x.dtype)


def feed_forward(cfg: FalconH1Config, p, x, valid=None):
    """Pre-norm SwiGLU over ``x`` [b, s, d], the gate's input times
    ``mlp_multipliers[0]`` and the output times ``[1]``; returns (the
    residual-added stream, no statistics)."""
    gate_m, down_m = cfg.mlp_multipliers
    with jax.named_scope(scopes.FFN):
        h = rms_norm(x, p["mlp_norm"], eps=cfg.rms_eps)
        gate = jax.nn.silu((h @ p["w_gate"]).astype(jnp.float32) * gate_m)
        gated = (gate * (h @ p["w_up"]).astype(jnp.float32)).astype(x.dtype)
        out = jnp.einsum("bsf,fd->bsd", gated, p["w_down"],
                         preferred_element_type=jnp.float32)
        return x + (out * down_m).astype(x.dtype), {}


def _mixer_in(cfg, p, x):
    """The mixer's input projection of the stream ``x`` [b, s, d]: (z
    [b, s, di] float32, xBC [b, s, C] in the model's dtype: what the
    convolution's tail keeps, dt [b, s, H] float32), multipliers on."""
    u = rms_norm(x, p["attn_norm"], eps=cfg.rms_eps)
    u = (u.astype(jnp.float32) * cfg.ssm_in_multiplier).astype(u.dtype)
    proj = jnp.einsum("bsd,dk->bsk", u, p["in_proj"],
                      preferred_element_type=jnp.float32) * mup_vector(cfg)
    z, xbc, dt = jnp.split(proj, [cfg.d_ssm, cfg.d_ssm + cfg.conv_dim],
                           axis=-1)
    return z, xbc.astype(x.dtype), dt


def plain_mixer_in(cfg, p, x):
    """``_mixer_in`` for a family whose mixer has no multipliers and
    whose layer's norm is ``p["norm"]`` (``models/nemotron_h.py``,
    ``models/granite_moe_hybrid.py``): the input projection of the stream
    ``x`` [b, s, d] under the layer's norm, (z [b, s, di] float32, xBC
    [b, s, C] in the model's dtype: what the convolution's tail keeps,
    dt [b, s, H] float32)."""
    u = rms_norm(x, p["norm"], eps=cfg.rms_eps)
    proj = jnp.einsum("bsd,dk->bsk", u, p["in_proj"],
                      preferred_element_type=jnp.float32)
    z, xbc, dt = jnp.split(proj, [cfg.d_ssm, cfg.d_ssm + cfg.conv_dim],
                           axis=-1)
    return z, xbc.astype(x.dtype), dt


def _mixer_split(cfg, p, conv, dt):
    """After the convolution (``conv`` [..., C] float32, bias on): silu,
    the split into x [..., H, P], B and C [..., G, N] in the model's
    dtype, and the step ``softplus(dt + dt_bias)``, the decay rate ``A``
    and the skip ``D``, float32."""
    lead = conv.shape[:-1]
    gn = cfg.ssm_groups * cfg.ssm_state
    act = jax.nn.silu(conv).astype(p["in_proj"].dtype)
    xs, b, c = jnp.split(act, [cfg.d_ssm, cfg.d_ssm + gn], axis=-1)
    xs = xs.reshape(*lead, cfg.ssm_heads, cfg.ssm_head_dim)
    b = b.reshape(*lead, cfg.ssm_groups, cfg.ssm_state)
    c = c.reshape(*lead, cfg.ssm_groups, cfg.ssm_state)
    step = jax.nn.softplus(dt + p["dt_bias"].astype(jnp.float32))
    return xs, b, c, step, -jnp.exp(p["A_log"].astype(jnp.float32))


def gated_norm(cfg, p, y, xs, z):
    """The skip ``D x`` onto ``y`` [..., H, P] float32, the gate
    ``silu(z)`` and the RMS norm in ``ssm_groups`` groups (gate first,
    norm second): what ``out_proj`` takes, in its dtype."""
    lead = y.shape[:-2]
    y = y + p["D"].astype(jnp.float32)[:, None] * xs.astype(jnp.float32)
    y = y.reshape(*lead, cfg.d_ssm) * jax.nn.silu(z)
    g = y.reshape(*lead, cfg.ssm_groups, cfg.d_ssm // cfg.ssm_groups)
    g = g * lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True)
                      + cfg.rms_eps)
    return (g.reshape(*lead, cfg.d_ssm) * p["ssm_norm"].astype(jnp.float32)
            ).astype(p["out_proj"].dtype)


def _mixer_out(cfg, p, y, xs, z):
    """The mixer's end: ``gated_norm``, ``out_proj``,
    ``ssm_out_multiplier``. Returns the term the block adds to its
    stream, in the model's dtype."""
    y = gated_norm(cfg, p, y, xs, z)
    out = jnp.einsum("...k,kd->...d", y, p["out_proj"],
                     preferred_element_type=jnp.float32)
    return (out * cfg.ssm_out_multiplier).astype(p["out_proj"].dtype)


_ENDS = (_mixer_in, _mixer_out)


def recurrent_mixer(cfg, p, x, state, valid, *, ends=_ENDS):
    """The mixer over a padded block: the stream ``x`` [n, t, d] (the
    block's input; the mixer norms it as the attention does), each row's
    ``state`` (S [n, H, P, N] float32, tail [n, K-1, C]) before its
    first token, ``valid`` [n, t] marking the positions that hold a
    token (a prefix of each row). Returns (the term to add to the
    stream [n, t, d], the state after each row's LAST VALID token).
    Padding moves nothing: the step is zeroed there (``ops/ssm.py``) and
    the tail is read at the row's length. ``ends``: the mixer's two ends
    (its norm and input projection, as ``_mixer_in``; its output
    projection after ``gated_norm``, as ``_mixer_out``), for another
    family's mixer that is this one between them
    (``models/nemotron_h.py``: no multipliers)."""
    mixer_in, mixer_out = ends
    s0, tail = state
    with jax.named_scope(scopes.SSM_MIXER):
        z, xbc, dt = mixer_in(cfg, p, x)
        conv = causal_conv(xbc, tail, p["conv_w"], p["conv_b"])
        xs, b, c, step, a = _mixer_split(cfg, p, conv, dt)
        step = jnp.where(valid[..., None], step, 0.0)
        y, s1 = ssm_scan(xs, step, a, b, c, s0, chunk=cfg.ssm_chunk)
        lengths = jnp.sum(valid, axis=1, dtype=jnp.int32)
        return (mixer_out(cfg, p, y, xs, z),
                (s1, last_rows(xbc, tail, lengths)))


def recurrent_step(cfg, p, x, state, layer, active, *, ends=_ENDS):
    """The mixer for one token a slot, over the slots' states where they
    lie: ``x`` [n, 1, d]; ``state`` the STACKED arrays (S [L, n, H, P,
    N] float32, tail [L, n, K-1, C]) of which this block's are at
    [layer]; ``active`` [n] bool. Returns (the term to add [n, 1, d],
    the stacked arrays): an active slot's state at [layer] advanced one
    token, an inactive slot's and every other layer's left bit for bit
    as they were. The float32 state goes through ``ssm_state_step`` (on
    a TPU one kernel that reads it once and writes it once, in place);
    the tail, 7 MB a layer at the published widths, is sliced and
    written back here. ``ends``: as ``recurrent_mixer``'s."""
    mixer_in, mixer_out = ends
    states, tails = state
    with jax.named_scope(scopes.SSM_MIXER):
        tail = tails[layer]
        z, xbc, dt = mixer_in(cfg, p, x)
        conv = causal_conv(xbc, tail, p["conv_w"], p["conv_b"])
        xs, b, c, step, a = _mixer_split(cfg, p, conv[:, 0], dt[:, 0])
        y, states = ssm_state_step(xs, step, a, b, c, states, layer, active)
        new_tail = jnp.concatenate([tail[:, 1:], xbc.astype(tail.dtype)],
                                   axis=1)
        tails = tails.at[layer].set(
            jnp.where(active[:, None, None], new_tail, tail))
        return mixer_out(cfg, p, y, xs, z[:, 0])[:, None], (states, tails)


def zero_state(cfg: FalconH1Config, rows: int) -> tuple:
    """The state of ``rows`` sequences before their first token."""
    (_, s_shape, s_dt), (_, t_shape, t_dt) = layer_plan(cfg)[0].state.arrays
    return (jnp.zeros((rows, *s_shape), s_dt),
            jnp.zeros((rows, *t_shape), t_dt))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def forward(cfg: FalconH1Config, params: dict, tokens):
    """Token ids [batch, seq] -> logits [batch, seq, vocab] (fp32): the
    plain causal path, every sequence from a zero state. ``seq`` is
    padded to whole scan chunks inside (and cut again)."""
    b, s = tokens.shape
    q = min(cfg.ssm_chunk, s)
    pad = (-s) % q
    tokens = jnp.pad(tokens, ((0, 0), (0, pad)))
    valid = jnp.broadcast_to(jnp.arange(s + pad) < s, (b, s + pad))
    x = embed(cfg, params, tokens)
    positions = jnp.arange(s + pad, dtype=jnp.int32)[None, :]
    sin, cos = rotary_tables(cfg, positions)["full"]
    start = jnp.zeros((b,), jnp.int32)
    state = zero_state(cfg, b)

    def block(x, p):
        q_, k, v = attention_projections(cfg, p, x, sin, cos)
        attn = cached_attention(q_, k, v, start, scale=cfg.head_dim ** -0.5)
        mixed, _ = recurrent_mixer(cfg, p, x, state, valid)
        x = attention_output(cfg, p, x, attn) + mixed
        x, _ = feed_forward(cfg, p, x)
        return x, None

    x, _ = lax.scan(block, x, params["blocks"])
    x = rms_norm(x, params["final_norm"], eps=cfg.rms_eps)[:, :s]
    return head_logits(cfg, params, x.reshape(b * s, -1)).reshape(b, s, -1)
