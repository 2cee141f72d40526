"""SmallThinker-family sparse-expert transformer (PowerInfer
SmallThinker-21BA3B-Instruct, ``model_name`` ``smallthinker_21b_instruct``),
for serving.

A decoder whose layers are of two kinds and whose router does not sit
where the other expert models' does:

- attention is full or sliding by layer (``sliding_window_layout``: 0, 1,
  1, 1 repeated). A FULL layer attends over its whole context and has NO
  rotary embedding (``rope_layout`` 0: position reaches it through the
  causal mask alone); a SLIDING layer sees the ``window`` newest keys
  (``i - window < j <= i``) and rotates the whole head, plain rotary at
  ``rope_theta``. Both have ``n_heads`` query heads over ``n_kv_heads`` KV
  heads of ``head_dim``, so one page pool serves every layer. No bias, no
  QK-norm;
- the feed-forward is ``n_experts`` routed experts, ``top_k`` a token, no
  shared expert, each a ReGLU: ``(relu(g @ gate) * (g @ up)) @ down`` of
  ``g``, the normed stream BEHIND the attention;
- the ROUTER reads ``h``, the layer's normed INPUT, the rows the
  attention's projections read: the ``top_k`` largest logits of ``h @
  router`` in float32, weighed by a softmax over those ``top_k`` alone
  (equal to the softmax over all ``n_experts``, renormalised over the
  chosen, which is how ``ops.moe.moe_route`` computes it). So the choice
  of a layer's experts is known before its attention runs, and the block
  keeps it across the attention: ``layer_plan`` states that of every run
  (``LayerStack.ahead``), ``feed_ahead`` makes the choice, ``feed_forward``
  takes it.

``params["blocks"]`` maps a run's key to the weights of that run of
identical consecutive layers, stacked on a leading axis (the published 52
layers are full x 1, sliding x 3, thirteen times); each layer's q, k and v
projections are ONE stack ``wqkv``, columns q | k | v
(``models/llama.py: fuse_attention_projections`` says why).

The block's pieces take no view on where keys and values live: ``forward``
puts plain causal attention between them, the paged serving engine its
page pool (``serve/engine_programs.py``). No training path: there are no
logical axes and no loss here.
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
from ray_tpu.ops.moe import moe_experts, moe_route, share_statistics
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.rope import apply_rope, rope_sin_cos

_PERIOD = (0, 1, 1, 1)


@dataclass(frozen=True)
class SmallThinkerConfig:
    vocab_size: int = 151936
    d_model: int = 2560
    sliding_window_layout: tuple = _PERIOD * 13   # 1: a sliding layer
    rope_layout: tuple = _PERIOD * 13             # 1: a layer that rotates
    n_heads: int = 28
    n_kv_heads: int = 4
    head_dim: int = 128
    window: int = 4096
    d_expert: int = 768               # one routed expert's width
    n_experts: int = 64
    top_k: int = 6
    norm_topk_prob: bool = True
    router_softmax: bool = True       # published true; false is refused
    rope_theta: float = 1500000.0
    rms_eps: float = 1e-6
    dtype: str = "bfloat16"
    tie_embeddings: bool = False

    def __post_init__(self):
        if tuple(self.rope_layout) != tuple(self.sliding_window_layout):
            raise ValueError(
                "rope_layout other than sliding_window_layout is not "
                "implemented: SmallThinker publishes one list twice (a "
                "sliding layer rotates, a full layer does not)")
        if not self.router_softmax:
            raise ValueError(
                "moe_primary_router_apply_softmax=false is not implemented "
                "(SmallThinker-21BA3B-Instruct publishes true)")

    @property
    def n_layers(self) -> int:
        return len(self.sliding_window_layout)

    @property
    def param_dtype(self):
        return jnp.dtype(self.dtype)


def smallthinker_21b_a3b() -> SmallThinkerConfig:
    """SmallThinker-21BA3B-Instruct as published: 52 layers, 64 experts a
    layer, 6 a token."""
    return SmallThinkerConfig()


def smallthinker_tiny(vocab_size: int = 128, **changes) -> SmallThinkerConfig:
    """Test-size config in float32: two periods, a window of 8, query
    groups of 7 as published, 8 experts 2 a token."""
    kw = dict(
        vocab_size=vocab_size, d_model=64, sliding_window_layout=_PERIOD * 2,
        rope_layout=_PERIOD * 2, n_heads=14, n_kv_heads=2, head_dim=16,
        window=8, d_expert=32, n_experts=8, top_k=2, dtype="float32")
    kw.update(changes)
    return SmallThinkerConfig(**kw)


# ---------------------------------------------------------------------------
# The layer plan
# ---------------------------------------------------------------------------

def _runs(cfg: SmallThinkerConfig) -> list:
    """Runs of consecutive layers of one attention kind: (key, sliding?,
    layers)."""
    runs = []
    for i, sliding in enumerate(cfg.sliding_window_layout):
        if runs and runs[-1][1] == bool(sliding):
            runs[-1][2] += 1
        else:
            runs.append([i, bool(sliding), 1])
    return [(f"layers{first}" + (f"-{first + n - 1}" if n > 1 else ""),
             sliding, n) for first, sliding, n in runs]


def layer_plan(cfg: SmallThinkerConfig) -> tuple:
    """The runs of identical layers, in order, as the serving engine's
    layer loop takes them: each run's key in ``params["blocks"]``, the
    kind of its attention (``full``: no rotary table, no window;
    ``sliding``: both) and its length. Every run's feed-forward takes the
    router's choice made of the layer's input (``ahead``)."""
    return tuple(
        LayerStack(key, "sliding" if sliding else "full",
                   cfg.window if sliding else None, n, ahead=True)
        for key, sliding, n in _runs(cfg))


def rotary_tables(cfg: SmallThinkerConfig, positions) -> dict:
    """(sin, cos) of ``positions`` for a sliding layer; a full layer has no
    rotary embedding and takes no table."""
    return {"full": (),
            "sliding": rope_sin_cos(positions, cfg.head_dim,
                                    theta=cfg.rope_theta)}


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

# Seeded weights that a bf16 program and a float32 reference can AGREE on,
# and on which each of the block's mechanisms still shows in the logits
# (the argument is ``models/laguna.py:init_params``'s; the numbers of this
# model are in ``benchmark/configs/smallthinker-21ba3b-instruct-d8.json``
# under ``assumed.init`` and in PERF.md):
# - the embedding's rows have unit variance, so that the stream is the
#   token's embedding plus sublayer outputs a quarter its size;
# - the query's columns of ``wqkv`` are at TWICE the fan-in scale: with
#   unit-variance scores a softmax over thousands of random keys is nearly
#   flat, its output a fortieth of a value's size, and neither the window
#   (536 of 4,632 keys at most) nor where the router reads shows in a
#   logit; with scores of deviation 2 some tens of keys carry a query and
#   attention adds a quarter of the stream (``wo`` at twice the fan-in
#   scale too, as Laguna's);
# - the experts' output projections are at 1/sqrt(layers) of the fan-in
#   scale (a residual branch's scaling), so that the routed part adds
#   about as much as the attention;
# - the router is at four times the fan-in scale: its softmax over the six
#   chosen is decisive (the sixth weighs a percent), so a choice that a
#   bf16 stream's rounding tips at the boundary moves nothing.
_EMBEDDING_STD = 1.0
_QUERY_GAIN = 2.0
_ATTENTION_OUT_GAIN = 2.0
_ROUTER_GAIN = 4.0


def init_params(cfg: SmallThinkerConfig, key) -> dict:
    """The parameter pytree: ``blocks`` maps each run's key to its stacked
    weights (the router in float32). Scales: the note above."""
    dt = cfg.param_dtype
    d, f, e = cfg.d_model, cfg.d_expert, cfg.n_experts
    qdim, kvdim = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim

    def dense(key, shape, fan_in, dtype=dt, gain=1.0):
        return (fanin_init(key, shape, fan_in) * gain).astype(dtype)

    runs = _runs(cfg)
    k_emb, k_head, *k_runs = jax.random.split(key, 2 + len(runs))
    blocks = {}
    for (name, _, n), k_run in zip(runs, k_runs):
        ks = jax.random.split(k_run, 7)
        blocks[name] = {
            "attn_norm": jnp.ones((n, d), dtype=dt),
            "wqkv": jnp.concatenate(
                [dense(ks[0], (n, d, qdim), d, gain=_QUERY_GAIN),
                 dense(ks[1], (n, d, 2 * kvdim), d)], axis=-1),
            "wo": dense(ks[2], (n, qdim, d), qdim,
                        gain=_ATTENTION_OUT_GAIN),
            "mlp_norm": jnp.ones((n, d), dtype=dt),
            "router": dense(ks[3], (n, d, e), d, dtype=jnp.float32,
                            gain=_ROUTER_GAIN),
            "wi_gate": dense(ks[4], (n, e, d, f), d),
            "wi_up": dense(ks[5], (n, e, d, f), d),
            "wo_e": dense(ks[6], (n, e, f, d), f * cfg.n_layers),
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

def attention_projections(cfg: SmallThinkerConfig, p, x, sin=None, cos=None):
    """What attention takes in, from the residual stream ``x`` [b, s, d]:
    pre-norm, q | k | v from the layer's one stack split into heads, and
    the rotary of a sliding layer on ``q`` and ``k`` (a full layer is
    handed no table and rotates nothing). Returns (q [b, s, heads, hd],
    k, v [b, s, kv heads, hd])."""
    b, s, _ = x.shape
    qdim, kvdim = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    with jax.named_scope(scopes.ATTN_QKV):
        h = rms_norm(x, p["attn_norm"], eps=cfg.rms_eps)
        q, k, v = (y.reshape(b, s, -1, cfg.head_dim) for y in jnp.split(
            h @ p["wqkv"], [qdim, qdim + kvdim], axis=-1))
        if sin is None:
            return q, k, v
        return apply_rope(q, sin, cos), apply_rope(k, sin, cos), v


def attention_output(cfg: SmallThinkerConfig, p, x, attn):
    """The attention sublayer's end: the heads' outputs ``attn`` ([b, s,
    heads, hd], or [b, heads, hd] of a one-token step) through ``wo``,
    added to ``x`` [b, s, d]."""
    b, s, _ = x.shape
    with jax.named_scope(scopes.ATTN_OUT):
        return x + attn.astype(x.dtype).reshape(b, s, -1) @ p["wo"]


def feed_ahead(cfg: SmallThinkerConfig, p, x):
    """What the feed-forward takes of the layer's INPUT ``x`` [b, s, d]:
    the router's choice for each token, made of the normed input (the
    norm is the one ``attention_projections`` takes: the compiler computes
    it once): ``ops.moe.moe_route``'s (weights, experts) of the b x s
    rows."""
    b, s, d = x.shape
    h = rms_norm(x, p["attn_norm"], eps=cfg.rms_eps)
    return moe_route(h.reshape(b * s, d), p["router"], top_k=cfg.top_k,
                     norm_topk_prob=cfg.norm_topk_prob)


def feed_forward(cfg: SmallThinkerConfig, p, x, valid=None, stacked=None, *,
                 ahead):
    """Pre-norm routed feed-forward over ``x`` [b, s, d], the stream behind
    the attention, by the choice ``ahead`` that ``feed_ahead`` made of the
    layer's input; returns (the residual-added stream, its statistics:
    how many experts got a token, the busiest one's load over the mean
    load). ``valid`` [b, s] marks the rows that are tokens: padding is
    sent to no expert and counts in no statistic. ``stacked``: (the run's
    weights stacked on their layer axis, this layer's index in them), from
    a program that scans the run: the expert stacks are then read from
    there in place, not from ``p``'s slices."""
    b, s, d = x.shape
    g = rms_norm(x, p["mlp_norm"], eps=cfg.rms_eps)
    held, layer = (p, None) if stacked is None else stacked
    out, load = moe_experts(
        g.reshape(b * s, d), ahead, held["wi_gate"], held["wi_up"],
        held["wo_e"], n_experts=cfg.n_experts, form="reglu", layer=layer,
        valid=None if valid is None else valid.reshape(b * s))
    stats = share_statistics(load, valid, b * s, cfg.top_k)
    del stats["routed_here_share"]      # every expert is held here
    with jax.named_scope(scopes.MOE_COMBINE):
        return x + out.reshape(b, s, d), stats


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def forward(cfg: SmallThinkerConfig, params: dict, tokens):
    """Token ids [batch, seq] -> logits [batch, seq, vocab] (fp32): the
    plain causal path, the runs of the layer plan one after another."""
    b, s = tokens.shape
    x = params["embedding"][tokens]
    positions = jnp.arange(s, dtype=jnp.int32)[None, :]
    tables = rotary_tables(cfg, positions)
    start = jnp.zeros((b,), jnp.int32)
    for run in layer_plan(cfg):

        def block(x, p, run=run):
            ahead = feed_ahead(cfg, p, x)
            q, k, v = attention_projections(cfg, p, x, *tables[run.kind])
            attn = cached_attention(q, k, v, start,
                                    scale=cfg.head_dim ** -0.5,
                                    window=run.window)
            x = attention_output(cfg, p, x, attn)
            x, _ = feed_forward(cfg, p, x, ahead=ahead)
            return x, None

        x, _ = lax.scan(block, x, params["blocks"][run.key])
    x = rms_norm(x, params["final_norm"], eps=cfg.rms_eps)
    return jnp.einsum("bsd,dv->bsv", x, lm_head_weights(cfg, params),
                      preferred_element_type=jnp.float32)
