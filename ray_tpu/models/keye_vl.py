"""Keye-VL-2.0-family decoder (Kwai-Keye Keye-VL-2.0-30B-A3B,
``model_type`` ``KeyeVL2``; the text path of the language model), for
serving.

Every layer is the same block, and every layer PICKS THE KEYS it attends
over among K/V rows:

- grouped-query attention, ``n_heads`` query heads on ``n_kv_heads`` KV
  heads of ``head_dim``, no bias; RMSNorm of ``q`` and of ``k`` over each
  HEAD's numbers (one weight vector of ``head_dim`` for all heads), then
  rotary on both: rotate-half over the whole head at ``rope_theta``, the
  frequency pairs taking their angle from one of THREE position axes by
  ``mrope_sections`` (M-RoPE, ``ops/rope.py:mrope_sin_cos``; a text
  token's axes are equal and this is plain rotary);
- an INDEXER (DeepSeek-V3.2's sparse attention, ``ops/index_select.py``):
  ``index_heads`` small heads of ``index_dim`` score every key a query
  may see, ``I(t, s) = sum_j w_j relu(qI_j(t) . kI(s))``, from ONE index
  key a token (``kI = layernorm(u WIk)``, which the token keeps beside
  its K and V rows) and index queries and head weights of the layer's
  normed input ``u`` (``qI = u WIq``, ``w = u WIw``), rotary over the
  whole index head by position axis 0; the layer's softmax, all its
  heads', runs over the ``index_topk`` keys of largest ``I`` alone;
- the feed-forward is ``n_experts`` routed experts, ``top_k`` a token, no
  shared expert, each a SwiGLU: softmax over all the router's logits in
  float32, the ``top_k`` largest, divided by their sum
  (``ops/moe.py:moe_ffn_dropless``).

``params["blocks"]`` holds the layers' weights stacked on a leading axis.
Everything a layer projects from its normed input is ONE stack ``w_in``,
columns q | k | v | index queries | index key | index weights: six
matmuls of one input as one, and no stack small enough for the compiler
to park on the core (``models/llama.py:fuse_attention_projections``).

The block's pieces take no view on where keys and values live
(``attention_projections``, ``index_projections``, ``attention_output``,
``feed_forward``): ``forward`` attends over the prompt's own rows, the
paged serving engine over its pools, whose plan (``layer_plan``) keeps
the K/V twins and the index key beside them. The vision tower is not
here: its configuration is not in the repository. No training path.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.llama import (  # noqa: F401 - embed, head_logits:
    LayerStack, embed, fanin_init,  # pieces of the block's module that
    head_logits, lm_head_weights)   # are Llama's
from ray_tpu.ops import scopes
from ray_tpu.ops.attention import cached_attention
from ray_tpu.ops.index_select import (MASKED, IndexInputs, causal,
                                      index_scores, kept)
from ray_tpu.ops.moe import moe_ffn_dropless, share_statistics
from ray_tpu.ops.norms import layer_norm, rms_norm
from ray_tpu.ops.paged_attention import PageRow
from ray_tpu.ops.rope import apply_rope, mrope_sin_cos, rope_sin_cos


@dataclass(frozen=True)
class KeyeVLConfig:
    vocab_size: int = 151936
    d_model: int = 2048
    n_layers: int = 48
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1e7
    mrope_sections: tuple = (16, 24, 24)   # frequency pairs by position axis
    index_heads: int = 16                  # the indexer
    index_dim: int = 64
    index_topk: int = 2048
    d_expert: int = 768                    # one routed expert's width
    n_experts: int = 128
    top_k: int = 8
    norm_topk_prob: bool = True
    rms_eps: float = 1e-6
    dtype: str = "bfloat16"
    tie_embeddings: bool = False

    def __post_init__(self):
        if sum(self.mrope_sections) != self.head_dim // 2:
            raise ValueError(
                f"mrope_sections {list(self.mrope_sections)} do not add "
                f"up to head_dim // 2 = {self.head_dim // 2}")

    @property
    def param_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def projected(self) -> tuple:
        """Where ``w_in``'s columns end: q, k, v, index queries, index
        key, index weights."""
        q, kv = self.n_heads * self.head_dim, self.n_kv_heads * self.head_dim
        widths = (q, kv, kv, self.index_heads * self.index_dim,
                  self.index_dim, self.index_heads)
        return tuple(sum(widths[:i + 1]) for i in range(len(widths)))


def keye_vl_2_30b_a3b() -> KeyeVLConfig:
    """Keye-VL-2.0-30B-A3B's language model as published: 48 layers, 128
    experts a layer, 8 a token, 2,048 keys a query."""
    return KeyeVLConfig()


def keye_vl_tiny(vocab_size: int = 128, **changes) -> KeyeVLConfig:
    """Test-size config in float32: three layers, query groups of 4, a
    selection of 8 keys, 8 experts 2 a token."""
    kw = dict(
        vocab_size=vocab_size, d_model=64, n_layers=3, n_heads=8,
        n_kv_heads=2, head_dim=16, mrope_sections=(2, 3, 3), index_heads=4,
        index_dim=8, index_topk=8, d_expert=32, n_experts=8, top_k=2,
        dtype="float32")
    kw.update(changes)
    return KeyeVLConfig(**kw)


# ---------------------------------------------------------------------------
# The layer plan
# ---------------------------------------------------------------------------

def layer_plan(cfg: KeyeVLConfig) -> tuple:
    """One run of ``n_layers`` identical layers, as the serving engine's
    layer loop takes it: the K/V twins and, BESIDE them, the indexer's
    key a token; a query attends over ``index_topk`` keys at most."""
    return (LayerStack(
        None, "full", None, cfg.n_layers, selects=cfg.index_topk,
        beside=(PageRow("index_key", cfg.index_dim, cfg.dtype),)),)


def rotary_tables(cfg: KeyeVLConfig, positions, axes=None) -> dict:
    """(sin, cos) of a head's rotary and of an index head's, for
    ``positions`` [b, s] of text tokens, whose three axes are equal; or,
    with ``axes`` [3, b, s], for tokens whose time, height and width
    differ (an image's or a video's: nothing hands the engine such yet).
    The index head rotates by axis 0."""
    if axes is None:
        axes = jnp.broadcast_to(positions, (3, *positions.shape))
    return {"full": (
        *mrope_sin_cos(axes, cfg.head_dim, cfg.mrope_sections,
                       theta=cfg.rope_theta),
        *rope_sin_cos(axes[0], cfg.index_dim, theta=cfg.rope_theta))}


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

# Seeded weights that a bf16 program and a float32 reference can AGREE on
# and on which every mechanism of the block still SHOWS in the logits (the
# argument is ``models/smallthinker.py:init_params``'s and
# ``models/dots3_note.py:init_params``'s; this model's numbers are a v5e's
# at the cell's widths, the check's two prompts of 4,600 tokens through
# ``scripts/check_seeds.py``, my chip runs, PR 60; the configuration's
# file repeats them under ``assumed.init``, PERF.md has the tables):
# - the embedding's rows have unit variance, so that the stream is the
#   token's embedding plus sublayer outputs a fraction of its size;
# - QK-norm leaves every head's q and k at unit RMS whatever ``w_in``'s
#   scale, so a head's scores have unit deviation and a softmax over
#   thousands of random keys is nearly flat: neither the selection (2,048
#   of 4,600 keys and more) nor the QK-norm itself would show in a logit.
#   The query's norm vector is ``_QUERY_GAIN`` (the key's stays one):
#   scores of that deviation, some tens of keys carrying a query. (At 3
#   every seed of four failed the check: 0.05-0.71.)
# - ``wo`` is at ``_ATTENTION_OUT_GAIN`` of the fan-in scale, and what
#   decides it is a CASCADE through the selection. A bf16 stream moves a
#   layer's index scores by a part in some hundreds; that tips some tens
#   of the keys nearest the selection's boundary in or out; whatever the
#   softmax's peakedness that moves the layer's attention output by about
#   a tenth (``sqrt(tipped / kept)``); the stream moves by attention's
#   share of it, and the NEXT layer's index scores with the stream. With
#   attention a third of the stream (``wo`` at 2) the six layers feed each
#   other and three seeds of four read 0.14-0.16 against the harness's
#   0.1; at 0.5 one seed of sixteen read 0.107; at 0.35 sixteen seeds read
#   0.000-0.034. What a reference BLIND to the indexer reads falls only
#   in proportion (0.24-0.51 at 2, 0.24-0.44 at 0.5, 0.12-0.17 at 0.35):
#   the room on that side is what the scale spends, and it is thin;
# - the index key's LayerNorm has a bias N(0, ``_INDEX_BIAS_STD``^2), so
#   that leaving the norm out moves the index scores (with weight one and
#   bias zero a LayerNorm of a random projection is nearly that
#   projection rescaled, which changes no ordering): 0.17-0.22;
# - the experts' output projections are at 1/sqrt(layers) of the fan-in
#   scale (a residual branch's scaling) and the router at
#   ``_ROUTER_GAIN`` times it. At 4 the chosen eight hold nearly all of
#   the softmax's mass and ``norm_topk_prob=false`` read 0.15 and 0.00:
#   no departure at all. At 2 they hold about three fifths of it
#   (renormalising shows: 0.32-0.52) and the eighth weighs a twenty-fifth
#   of the eight (a choice tipped at the boundary by a bf16 rounding
#   moves little).
_EMBEDDING_STD = 1.0
_QUERY_GAIN = 2.0
_ATTENTION_OUT_GAIN = 0.35
_ROUTER_GAIN = 2.0
_INDEX_BIAS_STD = 0.5
_INDEX_NORM_EPS = 1e-6      # the index key's LayerNorm (DeepSeek-V3.2's)


def init_params(cfg: KeyeVLConfig, key) -> dict:
    """The parameter pytree: ``blocks`` holds the layers' stacked weights
    (the router in float32). Scales: the note above."""
    dt = cfg.param_dtype
    d, n, e, f = cfg.d_model, cfg.n_layers, cfg.n_experts, cfg.d_expert
    qdim = cfg.n_heads * cfg.head_dim

    def dense(key, shape, fan_in, dtype=dt, gain=1.0):
        return (fanin_init(key, shape, fan_in) * gain).astype(dtype)

    k_emb, k_head, *ks = jax.random.split(key, 9)
    blocks = {
        "attn_norm": jnp.ones((n, d), dtype=dt),
        "w_in": dense(ks[0], (n, d, cfg.projected[-1]), d),
        "q_norm": jnp.full((n, cfg.head_dim), _QUERY_GAIN, dtype=dt),
        "k_norm": jnp.ones((n, cfg.head_dim), dtype=dt),
        "index_norm": jnp.ones((n, cfg.index_dim), dtype=dt),
        "index_norm_bias": (_INDEX_BIAS_STD * jax.random.normal(
            ks[1], (n, cfg.index_dim), jnp.float32)).astype(dt),
        "wo": dense(ks[2], (n, qdim, d), qdim, gain=_ATTENTION_OUT_GAIN),
        "mlp_norm": jnp.ones((n, d), dtype=dt),
        "router": dense(ks[3], (n, d, e), d, dtype=jnp.float32,
                        gain=_ROUTER_GAIN),
        "wi_gate": dense(ks[4], (n, e, d, f), d),
        "wi_up": dense(ks[5], (n, e, d, f), d),
        "wo_e": dense(ks[6], (n, e, f, d), f * n),
    }
    params = {
        "embedding": dense(k_emb, (cfg.vocab_size, d), 1,
                           gain=_EMBEDDING_STD),
        "blocks": blocks,
        "final_norm": jnp.ones((d,), dtype=dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense(k_head, (d, cfg.vocab_size), d)
    return params


# ---------------------------------------------------------------------------
# The block, as four pieces
# ---------------------------------------------------------------------------

def _projected(cfg: KeyeVLConfig, p, x):
    """The normed input's projections through ``w_in`` [b, s, ...] in
    float32, apart: q, k, v, index queries, index key, index weights.
    (``attention_projections`` and ``index_projections`` both ask: the
    compiler computes it once.)"""
    h = rms_norm(x, p["attn_norm"], eps=cfg.rms_eps)
    y = jnp.einsum("bsd,de->bse", h, p["w_in"],
                   preferred_element_type=jnp.float32)
    return jnp.split(y, cfg.projected[:-1], axis=-1)


def attention_projections(cfg: KeyeVLConfig, p, x, sin, cos, *_):
    """What attention takes in, from the residual stream ``x`` [b, s, d]:
    pre-norm, q | k | v split into heads, RMSNorm of ``q`` and of ``k``
    over each head, rotary on both (``sin``, ``cos``: the head's M-RoPE
    tables; the index head's, behind them, are ``index_projections``').
    Returns (q [b, s, heads, hd], k, v [b, s, kv heads, hd])."""
    b, s, _ = x.shape
    with jax.named_scope(scopes.ATTN_QKV):
        q, k, v = (y.reshape(b, s, -1, cfg.head_dim)
                   for y in _projected(cfg, p, x)[:3])
        q = rms_norm(q, p["q_norm"], eps=cfg.rms_eps).astype(x.dtype)
        k = rms_norm(k, p["k_norm"], eps=cfg.rms_eps).astype(x.dtype)
        return apply_rope(q, sin, cos), apply_rope(k, sin, cos), v.astype(
            x.dtype)


def index_projections(cfg: KeyeVLConfig, p, x, *tables) -> IndexInputs:
    """What the layer's indexer takes of its tokens, from the residual
    stream ``x`` [b, s, d]: the index queries in heads and the ONE index
    key a token (layer-normed, with bias), both rotated over the whole
    index head by the tables behind the head's own, and the index heads'
    weights in float32."""
    b, s, _ = x.shape
    sin, cos = tables[2:]
    with jax.named_scope(scopes.ATTN_QKV):
        _, _, _, qi, ki, w = _projected(cfg, p, x)
        qi = qi.reshape(b, s, cfg.index_heads, cfg.index_dim)
        ki = layer_norm(ki, p["index_norm"], p["index_norm_bias"],
                        eps=_INDEX_NORM_EPS)
        return IndexInputs(
            apply_rope(qi, sin, cos).astype(x.dtype), w,
            apply_rope(ki[:, :, None], sin, cos)[:, :, 0].astype(x.dtype),
            cfg.index_topk)


def attention_output(cfg: KeyeVLConfig, p, x, attn):
    """The attention sublayer's end: the heads' outputs ``attn`` ([b, s,
    heads, hd], or [b, heads, hd] of a one-token step) through ``wo``,
    added to ``x`` [b, s, d]."""
    b, s, _ = x.shape
    with jax.named_scope(scopes.ATTN_OUT):
        return x + attn.astype(x.dtype).reshape(b, s, -1) @ p["wo"]


def feed_forward(cfg: KeyeVLConfig, p, x, valid=None, stacked=None):
    """Pre-norm routed feed-forward over ``x`` [b, s, d]; returns (the
    residual-added stream, its statistics: how many experts got a token,
    the busiest one's load over the mean load). ``valid`` [b, s] marks
    the rows that are tokens: padding is sent to no expert and counts in
    no statistic. ``stacked``: (the run's weights stacked on their layer
    axis, this layer's index in them), from a program that scans the run:
    the expert stacks are then read from there in place
    (``moe_ffn_dropless``'s ``layer``), not from ``p``'s slices."""
    b, s, d = x.shape
    g = rms_norm(x, p["mlp_norm"], eps=cfg.rms_eps)
    held, layer = (p, None) if stacked is None else stacked
    out, load = moe_ffn_dropless(
        g.reshape(b * s, d), p["router"], held["wi_gate"], held["wi_up"],
        held["wo_e"], layer=layer, top_k=cfg.top_k,
        norm_topk_prob=cfg.norm_topk_prob,
        valid=None if valid is None else valid.reshape(b * s))
    stats = share_statistics(load, valid, b * s, cfg.top_k)
    del stats["routed_here_share"]      # every expert is held here
    with jax.named_scope(scopes.MOE_COMBINE):
        return x + out.reshape(b, s, d), stats


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def forward(cfg: KeyeVLConfig, params: dict, tokens, *, axes=None):
    """Token ids [batch, seq] -> logits [batch, seq, vocab] (fp32): the
    plain causal path, each layer attending over the keys its indexer
    keeps for each query among the prompt's own. ``axes`` [3, batch,
    seq]: the tokens' three rotary positions where they differ."""
    b, s = tokens.shape
    x = params["embedding"][tokens]
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    tables = rotary_tables(cfg, positions, axes)["full"]
    start = jnp.zeros((b,), jnp.int32)

    def block(x, p):
        q, k, v = attention_projections(cfg, p, x, *tables)
        index = index_projections(cfg, p, x, *tables)
        seen = causal(start, s, start, s, None)
        if s > index.topk:
            chosen = jnp.where(
                seen, index_scores(index.q, index.weights, index.key), MASKED)
            seen = seen & kept(chosen, index.topk)
        attn = cached_attention(q, k, v, start, scale=cfg.head_dim ** -0.5,
                                seen=seen)
        x = attention_output(cfg, p, x, attn)
        x, _ = feed_forward(cfg, p, x)
        return x, None

    x, _ = lax.scan(block, x, params["blocks"])
    x = rms_norm(x, params["final_norm"], eps=cfg.rms_eps)
    return jnp.einsum("bsd,dv->bsv", x, lm_head_weights(cfg, params),
                      preferred_element_type=jnp.float32)
