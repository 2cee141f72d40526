"""LFM2-MoE-family hybrid decoder (Liquid AI LFM2-8B-A1B, ``model_type``
``lfm2_moe``), for serving.

Every layer is an operator and then a feed-forward, each under its own
pre-norm (``operator_norm``, ``ffn_norm``), the entry of ``layer_types``
saying which operator:

    h <- h + Op_i(rms(h, norm_i))
    h <- h + Ffn_i(rms(h, ffn_norm_i))

- ``conv``, a GATED SHORT CONVOLUTION: ``B | C | u = x W_in`` (three equal
  parts, in that order); ``g = B * u``; ``c_t = sum_j w[:, j] g_{t-K+1+j}``,
  a depthwise causal filter of ``conv_taps`` K = 3 taps a channel, zeros
  before the sequence's start, no bias, NO activation; ``y = (C * c)
  W_out``. What a sequence keeps in such a layer is the last ``K - 1``
  rows of ``g``, [2, d] in the model's dtype, and nothing else: no scan,
  no decay, no matrix a head, 8 KB a layer at the published width. The
  convolution and the tail's read are ``ops/ssm.py``'s (``causal_conv``,
  ``last_rows``: the Mamba-2 families' own, here with no bias). A state
  that small is worth a page's keeping (``RecurrentState.pages_keep``):
  the engine keeps, with every page, the tail at the page's END, and a
  prefix hit begins from it;
- ``full_attention``: grouped-query, causal, q | k | v from one stack
  ``wqkv``, an RMS norm over each head on q and on k (``q_norm``,
  ``k_norm``, a weight a channel of the head) BEFORE the rotary embedding
  (rotate-half over the whole head), scale ``head_dim ** -0.5``. The
  head is 64 wide: the engine's pools hold two KV heads a row
  (``ops/paged_attention.py:rows_of_heads``); this module states the head
  as published;
- the feed-forward: layers before ``n_dense_layers`` a SwiGLU of
  ``d_ff``; the others ``n_experts`` SwiGLU experts of ``d_expert``,
  ``top_k`` a token: ``s = sigmoid(h W_r)`` in float32, the ``top_k``
  largest of ``s + expert_bias`` CHOSEN, their weights ``s`` itself over
  ``sum + 1e-6`` (``ops.moe.moe_route`` told the epsilon), times
  ``routed_scale``; no shared expert.

The stream starts as ``embedding[ids]`` and ends in a final RMS norm and
a head TIED to the embedding, contracted over the embedding where it lies
([vocab, d]: ``head_logits``).

``layer_plan`` says what each run of consecutive identical layers holds
and does; ``params["blocks"]`` maps a run's key to its weights stacked on
a leading axis. The pieces carry ``jax.named_scope``s
(``ops/scopes.py``). No training path: no logical axes and no loss here.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models import falcon_h1
from ray_tpu.models.llama import LayerStack, fanin_init
from ray_tpu.ops import scopes
from ray_tpu.ops.attention import cached_attention
from ray_tpu.ops.moe import moe_experts, moe_route, share_statistics
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.paged_attention import page_attention_scale
from ray_tpu.ops.rope import apply_rope, rope_sin_cos
from ray_tpu.ops.ssm import causal_conv, last_rows

_PERIOD = ("full_attention", "conv", "conv", "conv")
# as published: c c | A c c c x 4 | A c c | A c c
_LAYER_TYPES = (("conv", "conv") + _PERIOD * 4 + _PERIOD[:3] + _PERIOD[:3])
ROUTE_EPS = 1e-6            # on the chosen scores' sum, as published


@dataclass(frozen=True)
class Lfm2MoeConfig:
    """LFM2-8B-A1B as published (``config.json``)."""
    vocab_size: int = 65536
    d_model: int = 2048
    layer_types: tuple = _LAYER_TYPES
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 64
    rope_theta: float = 1e6
    conv_taps: int = 3                # conv_L_cache
    state_chunk: int = 128            # the engine's page (no scan cuts)
    d_ff: int = 7168                  # a dense layer's SwiGLU
    n_dense_layers: int = 2
    d_expert: int = 1792              # one routed expert's width
    n_experts: int = 32
    top_k: int = 4
    norm_topk_prob: bool = True
    routed_scale: float = 1.0
    rms_eps: float = 1e-5
    dtype: str = "bfloat16"
    tie_embeddings: bool = True

    def __post_init__(self):
        if set(self.layer_types) - {"conv", "full_attention"}:
            raise ValueError(
                "a layer's operator is conv or full_attention, not "
                f"{sorted(set(self.layer_types) - {'conv', 'full_attention'})}")
        if self.conv_taps < 2:
            raise ValueError("a short convolution of one tap keeps no tail")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def param_dtype(self):
        return jnp.dtype(self.dtype)


def lfm2_8b_a1b() -> Lfm2MoeConfig:
    """As published: 24 layers, 18 ``conv`` to 6 ``full_attention``."""
    return Lfm2MoeConfig()


def lfm2_moe_tiny(vocab_size: int = 128, **changes) -> Lfm2MoeConfig:
    """Test-size config in float32 with the published STRUCTURE: heads of
    64 in pairs a row, 3 taps, two dense layers, then a period ``A c c
    c``; 8 experts, 2 a token."""
    kw = dict(vocab_size=vocab_size, d_model=256,
              layer_types=("conv", "conv") + _PERIOD, n_heads=4,
              n_kv_heads=2, head_dim=64, rope_theta=1e4, d_ff=192,
              n_dense_layers=2, d_expert=48, n_experts=8, top_k=2,
              state_chunk=8, dtype="float32")
    kw.update(changes)
    return Lfm2MoeConfig(**kw)


# ---------------------------------------------------------------------------
# The layer plan
# ---------------------------------------------------------------------------

def _runs(cfg: Lfm2MoeConfig) -> list:
    """Runs of consecutive layers of one operator and one feed-forward:
    (key, operator, whether routed, first layer, layers)."""
    runs = []
    for i, kind in enumerate(cfg.layer_types):
        routed = i >= cfg.n_dense_layers
        if runs and runs[-1][:2] == [kind, routed]:
            runs[-1][3] += 1
        else:
            runs.append([kind, routed, i, 1])
    return [(f"layers{first}" + (f"-{first + n - 1}" if n > 1 else ""),
             kind, routed, first, n) for kind, routed, first, n in runs]


def recurrent_state(cfg: Lfm2MoeConfig) -> falcon_h1.RecurrentState:
    """What a sequence keeps in one ``conv`` layer: the tail, and a page
    keeps it too."""
    return falcon_h1.RecurrentState(
        (("conv_tail", (cfg.conv_taps - 1, cfg.d_model), cfg.dtype),),
        cfg.state_chunk, pages_keep=True)


def layer_plan(cfg: Lfm2MoeConfig) -> tuple:
    """The runs in order: a short convolution and its tail, or attention
    and its K/V pages, and the feed-forward behind either."""
    state = recurrent_state(cfg)
    return tuple(
        LayerStack(key, "full", None, n,
                   state=state if kind == "conv" else None,
                   attends=kind == "full_attention", feeds=True)
        for key, kind, _, _, n in _runs(cfg))


def rotary_tables(cfg: Lfm2MoeConfig, positions) -> dict:
    return {"full": rope_sin_cos(positions, cfg.head_dim,
                                 theta=cfg.rope_theta)}


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

# Seeded weights on which a bf16 program and a float32 reference agree AND
# each named departure of the reference alone reads not correct
# (``benchmark/families/lfm2_moe.py``). The argument is
# ``models/granite_moe_hybrid.py:init_params``'s for the TIED head and
# ``models/nemotron_h.py``'s and ``models/keye_vl.py``'s for what follows;
# the numbers are a v5e's at the cell's widths through
# ``scripts/check_seeds.py`` (my chip runs, PR 67: 64 served tokens a seed,
# the second prompt behind 26 restored pages, against the harness's 0.1)
# and, for the choice among scales, a bf16 ``forward`` against the
# reference at a quarter and a half of the width on this sandbox's CPU
# (the relative error of the logits, which a width does not move: 0.033
# to 0.035 of their deviation at 512 and 1,024):
# - a head tied to a RANDOM embedding answers every token with itself (the
#   token's own row against itself is ``sqrt(d)`` times what it is against
#   another), so the branches outgrow the embedding ``sqrt(d)`` times over
#   in TWO steps (grown evenly the network is chaotic). The two steps are
#   the two DENSE feed-forwards, each ``d ** 0.25`` times the stream that
#   enters it, and NOT the first two operators as granite's are: a gated
#   short convolution is CUBIC in its input (``C * conv(B * u)``, each of
#   the three linear in the normed stream), so it triples a relative
#   error where a SwiGLU doubles one, and a branch ``c`` times its stream
#   of gain ``g`` grows an error by ``sqrt((1 + c^2 g^2) / (1 + c^2))``:
#   2.97 a lead convolution against 1.98 a lead SwiGLU at ``c`` = 6.7. With
#   the operators leading and every other branch a third (the first
#   draw) four seeds of four read ``token_gap`` 0.49-0.91, 39-43 of 64
#   tokens not the reference's, every departure 1-3;
# - for the same reason a convolution's branch is ``_CONV_BRANCH`` of the
#   stream that enters it and every other branch ``_BRANCH`` (at 0.15 and
#   a third the error read 0.17 of the logits' deviation on the CPU, at
#   0.1 and 0.2 with the two scales below 0.033); eleven such branches
#   still move the logits by their whole deviation under ``order`` or
#   ``conv_act``. The embedding's rows are drawn so that the tied head's
#   logits have a deviation of ``_LOGIT_STD`` over the vocabulary: every
#   reading of the check and of the departures is proportional to it
#   (the stream is normed before every sublayer and before the head, so
#   it moves no token), and it stands where the check's worst seed of
#   twenty reads 0.044 and the least departure 0.15: the driver reads the
#   check in every run, the departures are read once;
# - the per-head norms leave q and k at unit RMS whatever ``wqkv``'s
#   scale, and with weights of one they COMMUTE with the rotary embedding
#   (a rotation keeps a head's norm) and are nearly a no-op on a random
#   projection: neither their presence nor their place would show. So
#   the norms' weights are log-normal over the head's channels
#   (``_QK_NORM_SPREAD``: a channel and its rotary partner weigh
#   differently), the query's times ``_QUERY_GAIN`` (at 2 a peaked
#   softmax added a third to the error, at 1.25 ``qk_norm`` and
#   ``norm_place`` still read 0.36-0.78; it stands at 1);
# - attention over many random keys averages its values away: ``wo`` is at
#   ``_ATTENTION_OUT_GAIN`` times its branch's scale;
# - a bf16 stream's error tips which expert a token takes in a few
#   percent of the token-layers whatever the router's scale, and a
#   sigmoid router's four chosen weigh about a quarter each, so a tipped
#   choice swaps a quarter of the routed part for that token: rare and
#   large, which is what a check that reads the WORST of 64 tokens sees.
#   The routed experts' ``wo_e`` stands at ``_ROUTED_OUT_GAIN`` of the
#   branch's scale (at 1 the tips were four fifths of the error; at 0.5
#   they are its half). The router is at ``_ROUTER_GAIN`` times the fan-in
#   scale: at 2 the chosen sigmoids lie near one and nearly equal, where
#   a softmax's weights over the same four differ severalfold, so
#   ``scores="softmax"`` shows (0.16-0.20 at 1, 0.24-0.28 at 2 with the
#   logits a third smaller) and a tip costs no more;
# - the expert bias is N(``_ROUTER_BIAS_MEAN``, ``_ROUTER_BIAS_STD`` ** 2).
#   Its spread changes the choice for most tokens. Its MEAN moves no
#   choice and no published weight (the scores alone are weighed), and is
#   what makes ``weights="with_bias"`` show: the chosen four's scores lie
#   within 0.6-0.9 of each other, so weights taken from ``s + bias`` with
#   a bias of mean zero differ by a fifth of a quarter, as much as one
#   tipped choice (0.05-0.11 on the CPU whatever the routed gain); with
#   the chosen ``s + bias`` drawn towards zero the weights spread and
#   change sign (at -0.7 the departure read 0.08 and 0.22 on the chip, at
#   -1 0.33-3.0). A trained model's buffer has whatever mean its
#   balancing left it.
# At these scales twenty seeds not seen before read ``token_gap``
# 0.000-0.044 (median 0.0075), all with 26 pages hit, and the nine
# departures 0.15-2.26 on two of them (``benchmark/configs/
# lfm2-8b-a1b-d14.json``, ``assumed.init``, has each).
_LOGIT_STD = 0.35
_CONV_BRANCH = 0.1
_BRANCH = 0.2
_QUERY_GAIN = 1.0
_QK_NORM_SPREAD = 0.5
_ATTENTION_OUT_GAIN = 4.0
_ROUTED_OUT_GAIN = 0.4
_ROUTER_GAIN = 2.0
_ROUTER_BIAS_STD = 0.5
_ROUTER_BIAS_MEAN = -1.0


def _embedding_std(cfg: Lfm2MoeConfig) -> float:
    """What the embedding's entries are drawn at: the tied head's logits
    then have a deviation of ``_LOGIT_STD`` over the vocabulary."""
    return _LOGIT_STD * cfg.d_model ** -0.5


def _branch_sizes(cfg: Lfm2MoeConfig):
    """The rms each layer's two branches are drawn to, [layers, 2] (the
    operator's, the feed-forward's), in units of the stream's start: the
    two DENSE feed-forwards ``d ** 0.25`` times the stream that enters
    them, a short convolution ``_CONV_BRANCH`` of the stream that enters
    it, every other branch ``_BRANCH`` of it (the note above)."""
    lead, stream, sizes = cfg.d_model ** 0.25, 1.0, []
    for layer, kind in enumerate(cfg.layer_types):
        op = stream * (_CONV_BRANCH if kind == "conv" else _BRANCH)
        stream = math.hypot(stream, op)
        ffn = stream * (lead if layer < min(2, cfg.n_dense_layers)
                        else _BRANCH)
        stream = math.hypot(stream, ffn)
        sizes.append((op, ffn))
    return jnp.array(sizes, jnp.float32)


def _init_run(cfg: Lfm2MoeConfig, kind: str, routed: bool, first: int,
              n: int, key) -> dict:
    dt = cfg.param_dtype
    d, hd = cfg.d_model, cfg.head_dim
    op_out, ffn_out = (_branch_sizes(cfg)[first:first + n].T
                       * _embedding_std(cfg))

    def dense(key, shape, fan_in, dtype=dt, gain=1.0):
        return (fanin_init(key, shape, fan_in) * gain).astype(dtype)

    ks = jax.random.split(key, 9)
    p = {"norm": jnp.ones((n, d), dtype=dt),
         "ffn_norm": jnp.ones((n, d), dtype=dt)}
    if kind == "conv":
        p.update(
            in_proj=dense(ks[0], (n, d, 3 * d), d),
            conv_w=dense(ks[1], (n, d, cfg.conv_taps), cfg.conv_taps),
            out_proj=dense(ks[2], (n, d, d), d,
                           gain=op_out[:, None, None]))
    else:
        qdim, kvdim = cfg.n_heads * hd, cfg.n_kv_heads * hd
        spread = jnp.exp(_QK_NORM_SPREAD * jax.random.normal(
            ks[3], (2, n, hd), jnp.float32))
        p.update(
            wqkv=dense(ks[0], (n, d, qdim + 2 * kvdim), d),
            q_norm=(spread[0] * _QUERY_GAIN).astype(dt),
            k_norm=spread[1].astype(dt),
            wo=dense(ks[2], (n, qdim, d), qdim,
                     gain=op_out[:, None, None] * _ATTENTION_OUT_GAIN))
    if routed:
        e, f = cfg.n_experts, cfg.d_expert
        p.update(
            router=dense(ks[4], (n, d, e), d, dtype=jnp.float32,
                         gain=_ROUTER_GAIN),
            router_bias=_ROUTER_BIAS_MEAN + _ROUTER_BIAS_STD
            * jax.random.normal(ks[5], (n, e), jnp.float32),
            wi_gate=dense(ks[6], (n, e, d, f), d),
            wi_up=dense(ks[7], (n, e, d, f), d),
            wo_e=dense(ks[8], (n, e, f, d), f,
                       gain=ffn_out[:, None, None, None] * _ROUTED_OUT_GAIN))
    else:
        p.update(
            w_gate=dense(ks[6], (n, d, cfg.d_ff), d),
            w_up=dense(ks[7], (n, d, cfg.d_ff), d),
            w_down=dense(ks[8], (n, cfg.d_ff, d), cfg.d_ff,
                         gain=ffn_out[:, None, None]))
    return p


def init_params(cfg: Lfm2MoeConfig, key) -> dict:
    """The parameter pytree: ``blocks`` maps each run's key to its stacked
    weights (the router and its bias in float32). Scales: the note
    above."""
    dt = cfg.param_dtype
    runs = _runs(cfg)
    k_emb, k_head, *k_runs = jax.random.split(key, 2 + len(runs))
    d = cfg.d_model
    params = {
        "embedding": (fanin_init(k_emb, (cfg.vocab_size, d), 1)
                      * _embedding_std(cfg)).astype(dt),
        "blocks": {name: _init_run(cfg, kind, routed, first, n, k)
                   for (name, kind, routed, first, n), k
                   in zip(runs, k_runs)},
        "final_norm": jnp.ones((d,), dtype=dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = (fanin_init(k_head, (d, cfg.vocab_size), 1)
                             * _embedding_std(cfg)).astype(dt)
    return params


# ---------------------------------------------------------------------------
# The layers' pieces
# ---------------------------------------------------------------------------

def embed(cfg: Lfm2MoeConfig, params, tokens):
    """Token ids -> the stream's start."""
    with jax.named_scope(scopes.EMBED):
        return params["embedding"][tokens]


def head_logits(cfg: Lfm2MoeConfig, params, x):
    """Normed last hidden states [b, d] -> float32 logits [b, vocab]. A
    tied head contracts over the embedding's own layout [vocab, d]: no
    transpose of it is built."""
    with jax.named_scope(scopes.LM_HEAD):
        if cfg.tie_embeddings:
            return jnp.einsum("bd,vd->bv", x, params["embedding"],
                              preferred_element_type=jnp.float32)
        return jnp.einsum("bd,dv->bv", x, params["lm_head"],
                          preferred_element_type=jnp.float32)


def _gate_in(cfg, p, x):
    """The convolution's input and its output's gate, from the stream
    ``x`` [n, t, d]: (``g = B * u`` in the model's dtype, which is what
    the tail keeps, C float32)."""
    h = rms_norm(x, p["norm"], eps=cfg.rms_eps)
    b, c, u = jnp.split(jnp.einsum("ntd,dk->ntk", h, p["in_proj"],
                                   preferred_element_type=jnp.float32),
                        3, axis=-1)
    return (b * u).astype(x.dtype), c


def _gate_out(p, c, conv):
    """``(C * conv) W_out``: the term the layer adds to its stream."""
    y = (c * conv).astype(p["out_proj"].dtype)
    return jnp.einsum("ntk,kd->ntd", y, p["out_proj"],
                      preferred_element_type=jnp.float32
                      ).astype(p["out_proj"].dtype)


def recurrent_mixer(cfg: Lfm2MoeConfig, p, x, state, valid,
                    page_ends: int | None = None):
    """A ``conv`` layer's operator over a padded block: the stream ``x``
    [n, t, d], each row's ``state`` (the tail [n, K-1, d] before its
    first token), ``valid`` [n, t] marking the positions that hold a
    token (a prefix of each row). Returns (the term to add to the stream
    [n, t, d], the state after each row's LAST VALID token) and, with
    ``page_ends`` (a page's tokens; a row then starts at a page's edge),
    third, the state at the END of every whole page of the block, ([n, t
    // page_ends, K-1, d],): the last ``K - 1`` rows of ``g`` of each
    page, a strided slice (a page is never shorter than the tail).
    Padding moves nothing: the filter is causal and the tail is read at
    the row's length."""
    (tail,) = state
    keep = tail.shape[1]
    with jax.named_scope(scopes.SSM_MIXER), jax.named_scope(
            scopes.SHORT_CONV):
        g, c = _gate_in(cfg, p, x)
        out = _gate_out(p, c, causal_conv(g, tail, p["conv_w"], None))
        lengths = jnp.sum(valid, axis=1, dtype=jnp.int32)
        final = (last_rows(g, tail, lengths).astype(tail.dtype),)
        if page_ends is None:
            return out, final
        if page_ends < keep:
            raise ValueError(f"a page of {page_ends} tokens is shorter "
                             f"than the tail of {keep}")
        n, t, d = g.shape
        pages = t // page_ends
        ends = g[:, :pages * page_ends].reshape(n, pages, page_ends, d)
        return out, final, (ends[:, :, page_ends - keep:].astype(tail.dtype),)


def recurrent_step(cfg: Lfm2MoeConfig, p, x, state, layer, active):
    """A ``conv`` layer's operator for one token a slot over the slots'
    STACKED tails [L, n, K-1, d], this layer's at [layer] (its place among
    the layers that keep state): ``x`` [n, 1, d], ``active`` [n] bool.
    Returns (the term to add [n, 1, d], the stacked tails): an active
    slot's tail at [layer] advanced one token, an inactive slot's and
    every other layer's left bit for bit as they were."""
    (tails,) = state
    with jax.named_scope(scopes.SSM_MIXER), jax.named_scope(
            scopes.SHORT_CONV):
        tail = tails[layer]
        g, c = _gate_in(cfg, p, x)
        out = _gate_out(p, c, causal_conv(g, tail, p["conv_w"], None))
        new_tail = jnp.concatenate([tail[:, 1:], g.astype(tail.dtype)],
                                   axis=1)
        tails = tails.at[layer].set(
            jnp.where(active[:, None, None], new_tail, tail))
        return out, (tails,)


def attention_projections(cfg: Lfm2MoeConfig, p, x, sin, cos):
    """What a ``full_attention`` layer's attention takes in, from the
    stream ``x`` [b, s, d]: the layer's norm, q | k | v from the one
    stack, in heads; an RMS norm over each head of q and of k, THEN
    rotary over the whole head; one rounding to the model's dtype.
    Returns (q [b, s, heads, hd], k, v [b, s, kv heads, hd])."""
    b, s, _ = x.shape
    qdim, kvdim = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    with jax.named_scope(scopes.ATTN_QKV):
        h = rms_norm(x, p["norm"], eps=cfg.rms_eps)
        q, k, v = (y.reshape(b, s, -1, cfg.head_dim) for y in jnp.split(
            jnp.einsum("bsd,dk->bsk", h, p["wqkv"],
                       preferred_element_type=jnp.float32),
            [qdim, qdim + kvdim], axis=-1))
        q = rms_norm(q, p["q_norm"], eps=cfg.rms_eps)
        k = rms_norm(k, p["k_norm"], eps=cfg.rms_eps)
        return (apply_rope(q, sin, cos).astype(x.dtype),
                apply_rope(k, sin, cos).astype(x.dtype), v.astype(x.dtype))


def attention_output(cfg: Lfm2MoeConfig, p, x, attn):
    """A ``full_attention`` layer's operator's end: the heads' outputs
    through ``wo``, added to ``x`` [b, s, d]."""
    b, s, _ = x.shape
    with jax.named_scope(scopes.ATTN_OUT):
        return x + jnp.einsum(
            "bsq,qd->bsd", attn.reshape(b, s, -1), p["wo"],
            preferred_element_type=jnp.float32).astype(x.dtype)


def feed_forward(cfg: Lfm2MoeConfig, p, x, valid=None, stacked=None):
    """A layer's second sublayer over ``x`` [b, s, d]: a dense SwiGLU
    (a run whose weights hold no router: no statistics), or the routed
    experts for the tokens routed to them, SwiGLU each; returns (the
    residual-added stream, statistics as ``models/laguna.py:
    feed_forward``'s). ``valid`` [b, s] marks the rows that are tokens.
    ``stacked``: (the run's weights stacked on their layer axis, this
    layer's index in them), from a program that scans the run: the expert
    stacks are then read from there in place (``moe_experts``'s
    ``layer``), not from ``p``'s slices."""
    b, s, d = x.shape
    h = rms_norm(x, p["ffn_norm"], eps=cfg.rms_eps)
    if "router" not in p:
        with jax.named_scope(scopes.FFN):
            gated = jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])
            return x + jnp.einsum(
                "bsf,fd->bsd", gated, p["w_down"],
                preferred_element_type=jnp.float32).astype(x.dtype), {}
    held, layer = (p, None) if stacked is None else stacked
    rows = h.reshape(b * s, d)
    flat = None if valid is None else valid.reshape(b * s)
    choice = moe_route(
        rows, p["router"], top_k=cfg.top_k,
        norm_topk_prob=cfg.norm_topk_prob, routed_scale=cfg.routed_scale,
        scoring="sigmoid", choice_bias=p["router_bias"], norm_eps=ROUTE_EPS)
    routed, load = moe_experts(
        rows, choice, held["wi_gate"], held["wi_up"], held["wo_e"],
        n_experts=cfg.n_experts, valid=flat, form="swiglu", layer=layer)
    stats = share_statistics(load, valid, b * s, cfg.top_k)
    del stats["routed_here_share"]      # every expert is held here
    with jax.named_scope(scopes.MOE_COMBINE):
        return x + routed.reshape(b, s, d), stats


def zero_state(cfg: Lfm2MoeConfig, rows: int) -> tuple:
    """The state of ``rows`` sequences in one ``conv`` layer before their
    first token."""
    return tuple(jnp.zeros((rows, *shape), dtype)
                 for _, shape, dtype in recurrent_state(cfg).arrays)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def forward(cfg: Lfm2MoeConfig, params: dict, tokens):
    """Token ids [batch, seq] -> logits [batch, seq, vocab] (fp32): the
    plain causal path, the runs of the layer plan one after another,
    every sequence from a zero state."""
    b, s = tokens.shape
    valid = jnp.ones((b, s), bool)
    x = embed(cfg, params, tokens)
    sin, cos = rotary_tables(
        cfg, jnp.arange(s, dtype=jnp.int32)[None, :])["full"]
    start = jnp.zeros((b,), jnp.int32)
    state = zero_state(cfg, b)
    for run in layer_plan(cfg):

        def block(x, p, run=run):
            if run.attends:
                q, k, v = attention_projections(cfg, p, x, sin, cos)
                attn = cached_attention(
                    q, k, v, start, scale=page_attention_scale(cfg.head_dim))
                x = attention_output(cfg, p, x, attn)
            else:
                x = x + recurrent_mixer(cfg, p, x, state, valid)[0]
            x, _ = feed_forward(cfg, p, x, valid=valid)
            return x, None

        x, _ = lax.scan(block, x, params["blocks"][run.key])
    x = rms_norm(x, params["final_norm"], eps=cfg.rms_eps)
    return head_logits(cfg, params, x.reshape(b * s, -1)).reshape(b, s, -1)
