"""Laguna-family sparse-expert transformer (poolside Laguna-S-2.1,
``model_type`` ``laguna``), for serving.

A decoder whose layers are NOT one block repeated:

- attention is of two kinds (``layer_types``). A ``full_attention`` layer
  attends over its whole context with ``n_heads`` query heads and rotates
  the first ``partial_rotary`` of each head by YaRN-scaled frequencies
  (sin and cos times ``yarn_attention_factor``; the rest of the head
  passes through); a ``sliding_attention`` layer sees the ``window``
  newest keys, has ``n_heads_sliding`` query heads and plain rotary over
  the whole head. Both share ``n_kv_heads`` KV heads of ``head_dim``, so
  one page pool serves every layer;
- every head's attention output is scaled by a gate of its own before
  ``wo``: ``sigmoid(h @ wg)``, ``h`` the normed input of the sublayer
  (``gating: per-head``);
- the feed-forward of the layers in ``mlp_only_layers`` is a dense SwiGLU;
  every other layer's is ``n_experts`` routed experts, ``top_k`` a token,
  their softmax weights renormalised (``norm_topk_prob``) and times
  ``routed_scale``, PLUS one shared expert on every token. Of the routed
  experts this process may hold a share: ``n_experts_held`` of them from
  ``first_expert``, as one chip of an expert-parallel group does. It
  routes over all ``n_experts`` and computes its own experts' part
  (``ops.moe.moe_ffn_dropless``); nothing stands in for the others.

So the parameters are not one stack. ``params["blocks"]`` maps a run's key
to the weights of that run of identical consecutive layers, stacked on a
leading axis, and ``layer_plan`` lists the runs in order with their kind:
the published 48 layers are a leading dense full layer, then sliding x 3,
full x 1, ... Each run's q, k and v projections are ONE stack ``wqkv``
(columns q | k | v): a run of three layers' ``wk`` alone (18.9 MB) is
small enough for the compiler to park on the core and move round every
attention kernel (``models/llama.py: fuse_attention_projections``).

The block's pieces take no view on where keys and values live
(``attention_projections``, ``attention_output``, ``feed_forward``):
``forward`` puts plain causal attention between them, the paged serving
engine its page pool (``serve/paged_llm.py``). No training path: there are
no logical axes and no loss here.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.llama import (  # noqa: F401 - embed, head_logits:
    LayerStack, embed, fanin_init,  # pieces of the block's module that
    head_logits, lm_head_weights)   # are Llama's
from ray_tpu.ops import scopes
from ray_tpu.ops.attention import cached_attention
from ray_tpu.ops.moe import moe_ffn_dropless, share_statistics
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.rope import apply_rope, rope_sin_cos

_PERIOD = ("full_attention", "sliding_attention", "sliding_attention",
           "sliding_attention")


@dataclass(frozen=True)
class LagunaConfig:
    vocab_size: int = 100352
    d_model: int = 3072
    layer_types: tuple = _PERIOD * 12
    mlp_only_layers: tuple = (0,)
    n_heads: int = 48                 # query heads of a full layer
    n_heads_sliding: int = 72         # of a sliding layer
    n_kv_heads: int = 8
    head_dim: int = 128
    window: int = 512
    d_ff: int = 12288                 # a dense layer's width
    d_expert: int = 1024              # one routed expert's
    d_shared: int = 1024              # the shared expert's
    n_experts: int = 256              # the router's width
    n_experts_held: int = 256         # experts whose weights are here,
    first_expert: int = 0             # from this one
    top_k: int = 10
    norm_topk_prob: bool = True
    routed_scale: float = 2.5
    rope_theta: float = 500000.0      # full layers: YaRN over the first
    partial_rotary: float = 0.5       # half of each head
    yarn_factor: float = 128.0
    yarn_original_len: int = 8192
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 1.4852030263919618
    rope_theta_sliding: float = 10000.0
    rms_eps: float = 1e-6
    dtype: str = "bfloat16"
    tie_embeddings: bool = False

    def __post_init__(self):
        if not 0 <= self.first_expert <= self.n_experts - self.n_experts_held:
            raise ValueError(
                f"experts {self.first_expert} to "
                f"{self.first_expert + self.n_experts_held} are not among "
                f"the router's {self.n_experts}")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def param_dtype(self):
        return jnp.dtype(self.dtype)


def laguna_s_2_1() -> LagunaConfig:
    """Laguna-S-2.1 as published: 48 layers, every expert held."""
    return LagunaConfig()


def laguna_tiny(vocab_size: int = 128, **changes) -> LagunaConfig:
    """Test-size config in float32: a leading layer and one period, query
    groups of 6 and 9 as published, 2 of 8 experts held."""
    kw = dict(
        vocab_size=vocab_size, d_model=64, layer_types=(_PERIOD * 2)[:5],
        n_heads=12, n_heads_sliding=18, n_kv_heads=2, head_dim=16,
        window=16, d_ff=128, d_expert=32, d_shared=32, n_experts=8,
        n_experts_held=2, top_k=3, yarn_original_len=32, dtype="float32")
    kw.update(changes)
    return LagunaConfig(**kw)


# ---------------------------------------------------------------------------
# The layer plan
# ---------------------------------------------------------------------------

def _runs(cfg: LagunaConfig) -> list:
    """Runs of consecutive layers of one attention kind and one kind of
    feed-forward: (key, sliding?, dense?, layers)."""
    runs = []
    for i, kind in enumerate(cfg.layer_types):
        what = (kind == "sliding_attention", i in cfg.mlp_only_layers)
        if runs and tuple(runs[-1][1:3]) == what:
            runs[-1][3] += 1
        else:
            runs.append([i, *what, 1])
    return [(f"layers{first}" + (f"-{first + n - 1}" if n > 1 else ""),
             sliding, dense, n) for first, sliding, dense, n in runs]


def layer_plan(cfg: LagunaConfig) -> tuple:
    """The runs of identical layers, in order, as the serving engine's
    layer loop takes them: each run's key in ``params["blocks"]``, the
    kind of its attention (which rotary table, and the window or none)
    and its length."""
    return tuple(
        LayerStack(key, "sliding" if sliding else "full",
                   cfg.window if sliding else None, n)
        for key, sliding, _, n in _runs(cfg))


def _yarn_frequencies(cfg: LagunaConfig):
    """Inverse frequencies of a full layer's rotary pairs, as YaRN sets
    them (arXiv:2309.00071; Hugging Face ``_compute_yarn_parameters``):
    the fast pairs as they are, the slow ones divided by ``yarn_factor``,
    a linear ramp between the pairs that turn ``yarn_beta_fast`` and
    ``yarn_beta_slow`` times in ``yarn_original_len`` positions."""
    dim = int(cfg.head_dim * cfg.partial_rotary)
    base, orig = cfg.rope_theta, cfg.yarn_original_len

    def pair_turning(turns):
        return (dim * math.log(orig / (turns * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(pair_turning(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(pair_turning(cfg.yarn_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    pairs = jnp.arange(dim // 2, dtype=jnp.float32)
    kept = 1.0 - jnp.clip((pairs - low) / (high - low), 0.0, 1.0)
    plain = 1.0 / (base ** (2.0 * pairs / dim))
    return plain / cfg.yarn_factor * (1.0 - kept) + plain * kept


def rotary_tables(cfg: LagunaConfig, positions) -> dict:
    """(sin, cos) of ``positions`` for each kind of layer. A full layer's
    cover the first ``partial_rotary`` of a head only (their last axis is
    that many pairs) and carry YaRN's attention factor."""
    with jax.named_scope(scopes.ATTN_QKV):
        angles = (positions[..., None].astype(jnp.float32)
                  * _yarn_frequencies(cfg))
        f = cfg.yarn_attention_factor
        return {"full": (jnp.sin(angles) * f, jnp.cos(angles) * f),
                "sliding": rope_sin_cos(positions, cfg.head_dim,
                                        theta=cfg.rope_theta_sliding)}


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

# Seeded weights that a bf16 program and a float32 reference can AGREE on.
# With every matrix at the fan-in scale the residual stream is a sum of ten
# equal random vectors, the router's 256 logits have unit variance (the
# tenth and the eleventh lie 0.045 apart in the mean) and the ten chosen
# experts weigh alike: a bf16 stream's 1% of rounding then changes WHICH
# held expert a token takes for 7-19% of the tokens a layer, each change
# moves the logits by 0.09, and no bf16 program's greedy tokens stay within
# 0.1 of a float32 reference's best (v5e, the cell's widths, 768 positions:
# logit error 0.078 rms, 94 tokens not the reference's own, the worst 0.77
# short; with the reference's choice of experts forced, 0.024 and 0.095;
# Mistral-7B's twelve layers by the same measure 0.014 and 0.033). A trained
# model is not like that. So, as inits that scale the residual branches do:
# - the embedding's rows have unit variance, so that the stream is the
#   token's embedding plus sublayer outputs a third its size;
# - the feed-forwards' output projections (``w_down``, ``wo_e``,
#   ``ws_down``) are at 1/sqrt(2 x layers) of the fan-in scale (GPT-2's
#   residual scaling); attention over hundreds of random keys averages its
#   values to a tenth of a feed-forward's output, so ``wo`` is at TWICE the
#   fan-in scale and the two kinds of sublayer add alike to the stream;
# - the router is at four times the fan-in scale: its softmax is decisive
#   (the tenth chosen expert weighs a percent, not a tenth), so a choice
#   that tips at the boundary moves nothing.
# Then the program reads 0.007 rms and 0.015-0.049 at worst over 1,536
# positions, and a reference that leaves out the window, the gate, the
# routed scale or the softmax reads 0.6, 3.0, 0.6 and 1.0 short in the
# median block of 64 tokens (my chip runs, PR 33: PERF.md, Findings).
_EMBEDDING_STD = 1.0
_ATTENTION_OUT_GAIN = 2.0
_ROUTER_GAIN = 4.0


def init_params(cfg: LagunaConfig, key) -> dict:
    """The parameter pytree: ``blocks`` maps each run's key to its
    stacked weights (the router in float32). Scales: the note above."""
    dt = cfg.param_dtype
    d, kvdim = cfg.d_model, cfg.n_kv_heads * cfg.head_dim
    branches = 2 * cfg.n_layers          # residual branches in the stack

    def dense(key, shape, fan_in, dtype=dt, gain=1.0):
        return (fanin_init(key, shape, fan_in) * gain).astype(dtype)

    runs = _runs(cfg)
    k_emb, k_head, *k_runs = jax.random.split(key, 2 + len(runs))
    blocks = {}
    for (name, sliding, is_dense, n), k_run in zip(runs, k_runs):
        heads = cfg.n_heads_sliding if sliding else cfg.n_heads
        qdim = heads * cfg.head_dim
        ks = jax.random.split(k_run, 10)
        p = {
            "attn_norm": jnp.ones((n, d), dtype=dt),
            "wqkv": dense(ks[0], (n, d, qdim + 2 * kvdim), d),
            "wg": dense(ks[1], (n, d, heads), d),
            "wo": dense(ks[2], (n, qdim, d), qdim,
                        gain=_ATTENTION_OUT_GAIN),
            "mlp_norm": jnp.ones((n, d), dtype=dt),
        }
        if is_dense:
            p.update(w_gate=dense(ks[3], (n, d, cfg.d_ff), d),
                     w_up=dense(ks[4], (n, d, cfg.d_ff), d),
                     w_down=dense(ks[5], (n, cfg.d_ff, d),
                                  cfg.d_ff * branches))
        else:
            e, f, fs = cfg.n_experts_held, cfg.d_expert, cfg.d_shared
            p.update(
                router=dense(ks[3], (n, d, cfg.n_experts), d,
                             dtype=jnp.float32, gain=_ROUTER_GAIN),
                wi_gate=dense(ks[4], (n, e, d, f), d),
                wi_up=dense(ks[5], (n, e, d, f), d),
                wo_e=dense(ks[6], (n, e, f, d), f * branches),
                ws_gate=dense(ks[7], (n, d, fs), d),
                ws_up=dense(ks[8], (n, d, fs), d),
                ws_down=dense(ks[9], (n, fs, d), fs * branches))
        blocks[name] = p
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
# The block, as three pieces
# ---------------------------------------------------------------------------

def _rotate(x, sin, cos):
    """Rotary on the leading ``2 x sin.shape[-1]`` features of each head;
    the rest pass through."""
    rot = 2 * sin.shape[-1]
    if rot == x.shape[-1]:
        return apply_rope(x, sin, cos)
    with jax.named_scope(scopes.ATTN_QKV):
        return jnp.concatenate(
            [apply_rope(x[..., :rot], sin, cos), x[..., rot:]], axis=-1)


def attention_projections(cfg: LagunaConfig, p, x, sin, cos):
    """What attention takes in, from the residual stream ``x`` [b, s, d]:
    pre-norm, q | k | v from the layer's one stack split into heads (as
    many query heads as the stack is wide: a full and a sliding layer
    differ), the rotary of the layer's kind (``rotary_tables``) on ``q``
    and ``k``. Returns (q [b, s, heads, hd], k, v [b, s, kv heads, hd])."""
    b, s, _ = x.shape
    kvdim = cfg.n_kv_heads * cfg.head_dim
    qdim = p["wqkv"].shape[-1] - 2 * kvdim
    with jax.named_scope(scopes.ATTN_QKV):
        h = rms_norm(x, p["attn_norm"], eps=cfg.rms_eps)
        q, k, v = (y.reshape(b, s, -1, cfg.head_dim) for y in jnp.split(
            h @ p["wqkv"], [qdim, qdim + kvdim], axis=-1))
        return _rotate(q, sin, cos), _rotate(k, sin, cos), v


def attention_output(cfg: LagunaConfig, p, x, attn):
    """The attention sublayer's end: each head's output ``attn`` ([b, s,
    heads, hd], or [b, heads, hd] of a one-token step) times the head's
    gate, ``sigmoid(h @ wg)`` of the sublayer's normed input ``h``, then
    ``wo``, added to ``x`` [b, s, d]. (The norm of ``x`` is the one
    ``attention_projections`` took: the compiler computes it once.)"""
    b, s, _ = x.shape
    with jax.named_scope(scopes.ATTN_OUT):
        h = rms_norm(x, p["attn_norm"], eps=cfg.rms_eps)
        gate = jax.nn.sigmoid((h @ p["wg"]).astype(jnp.float32))  # [b,s,heads]
        attn = attn.reshape(b, s, -1, cfg.head_dim) * gate[..., None]
        return x + attn.astype(x.dtype).reshape(b, s, -1) @ p["wo"]


def feed_forward(cfg: LagunaConfig, p, x, valid=None, stacked=None):
    """Pre-norm feed-forward over ``x`` [b, s, d]; returns (the
    residual-added stream, its statistics). A dense layer (its weights say
    which) is a SwiGLU with no statistics. Any other: the held routed
    experts' part for the tokens routed to them, plus the shared expert on
    every token. ``valid`` [b, s] marks the rows that are tokens: padding
    is sent to no expert and counts in no statistic. The statistics are
    scalars of this call, over the HELD experts: how many got a token, the
    busiest one's load over the mean load, and the share of the tokens'
    ``top_k`` choices that fell on a held expert. ``stacked``: (the run's
    weights stacked on their layer axis, this layer's index in them), from
    a program that scans the run: the expert stacks are then read from
    there in place (``moe_ffn_dropless``'s ``layer``), not from ``p``'s
    slices."""
    b, s, d = x.shape
    h = rms_norm(x, p["mlp_norm"], eps=cfg.rms_eps)
    if "w_gate" in p:
        with jax.named_scope(scopes.FFN):
            gated = jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])
            return x + gated @ p["w_down"], {}
    held, layer = (p, None) if stacked is None else stacked
    routed, load = moe_ffn_dropless(
        h.reshape(b * s, d), p["router"], held["wi_gate"], held["wi_up"],
        held["wo_e"], layer=layer, top_k=cfg.top_k,
        norm_topk_prob=cfg.norm_topk_prob,
        routed_scale=cfg.routed_scale, first_expert=cfg.first_expert,
        valid=None if valid is None else valid.reshape(b * s))
    with jax.named_scope(scopes.SHARED_EXPERT):
        shared = (jax.nn.silu(h @ p["ws_gate"])
                  * (h @ p["ws_up"])) @ p["ws_down"]
    stats = share_statistics(load, valid, b * s, cfg.top_k)
    with jax.named_scope(scopes.MOE_COMBINE):
        return x + routed.reshape(b, s, d) + shared, stats


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def forward(cfg: LagunaConfig, params: dict, tokens):
    """Token ids [batch, seq] -> logits [batch, seq, vocab] (fp32): the
    plain causal path, the runs of the layer plan one after another."""
    b, s = tokens.shape
    x = params["embedding"][tokens]
    positions = jnp.arange(s, dtype=jnp.int32)[None, :]
    tables = rotary_tables(cfg, positions)
    start = jnp.zeros((b,), jnp.int32)
    for run in layer_plan(cfg):
        sin, cos = tables[run.kind]

        def block(x, p, run=run, sin=sin, cos=cos):
            q, k, v = attention_projections(cfg, p, x, sin, cos)
            attn = cached_attention(q, k, v, start,
                                    scale=cfg.head_dim ** -0.5,
                                    window=run.window)
            x = attention_output(cfg, p, x, attn)
            x, _ = feed_forward(cfg, p, x)
            return x, None

        x, _ = lax.scan(block, x, params["blocks"][run.key])
    x = rms_norm(x, params["final_norm"], eps=cfg.rms_eps)
    return jnp.einsum("bsd,dv->bsv", x, lm_head_weights(cfg, params),
                      preferred_element_type=jnp.float32)
