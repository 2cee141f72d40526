"""OLMoE-family sparse-expert transformer (OLMoE-1B-7B, arXiv:2409.02060).

Same skeleton as the Llama family (stacked blocks + ``lax.scan``,
logical-axis annotations), with two differences in the block:

- QK-norm: ``q`` and ``k`` pass through an RMSNorm over the WHOLE
  projection (all heads together, before the split into heads), then
  rotary. ``config.json`` has no key for it: it is in the published model
  code and the paper.
- the feed-forward is 64 routed experts, 8 a token, no shared expert,
  every token served by all of its experts (``ops.moe.moe_ffn_dropless``);
  the routing weights are the top-k softmax probabilities as they are
  unless ``norm_topk_prob``.

The block is stated as pieces that take no view on where keys and
values live, ``attention_projections``, ``attention_output`` and
``feed_forward`` (as ``models/llama.py`` states its own): ``forward`` puts causal attention
between them, the paged serving engine its page pool
(``serve/paged_llm.py``).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.llama import (  # noqa: F401 - parts of the block's module
    attention_output,   # the attention sublayer's end, the one run of
    layer_plan,         # identical layers, the one rotary table and the
    rotary_tables,      # head are Llama's
    embed,
    fanin_init,
    head_logits,
    lm_head_weights,
)
from ray_tpu.ops import scopes
from ray_tpu.ops.attention import attention
from ray_tpu.ops.moe import moe_ffn_dropless
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.rope import apply_rope, rope_sin_cos


@dataclass(frozen=True)
class OlmoeConfig:
    vocab_size: int = 50304
    d_model: int = 2048
    n_layers: int = 16
    n_heads: int = 16
    n_kv_heads: int = 16
    head_dim: int = 128
    d_ff: int = 1024                  # one expert's width
    n_experts: int = 64
    top_k: int = 8
    norm_topk_prob: bool = False
    clip_qkv: float | None = None     # published null; any other is refused
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    dtype: str = "bfloat16"
    tie_embeddings: bool = False

    def __post_init__(self):
        if self.clip_qkv is not None:
            raise ValueError(
                f"clip_qkv={self.clip_qkv!r}: clipping of q/k/v is not "
                "implemented (OLMoE-1B-7B publishes null)")

    @property
    def param_dtype(self):
        return jnp.dtype(self.dtype)


def olmoe_1b_7b() -> OlmoeConfig:
    """OLMoE-1B-7B-0125-Instruct as published: 6.92B parameters, 1.3B a
    token."""
    return OlmoeConfig()


def olmoe_tiny(vocab_size: int = 128) -> OlmoeConfig:
    """Test-size config in float32."""
    return OlmoeConfig(
        vocab_size=vocab_size, d_model=64, n_layers=2, n_heads=4,
        n_kv_heads=4, head_dim=16, d_ff=32, n_experts=8, top_k=3,
        dtype="float32")


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def param_logical_axes(cfg: OlmoeConfig) -> dict:
    block = {
        "attn_norm": (None, "embed"),
        "wq": (None, "embed", "heads"),
        "wk": (None, "embed", "kv_heads"),
        "wv": (None, "embed", "kv_heads"),
        "q_norm": (None, "heads"),
        "k_norm": (None, "kv_heads"),
        "wo": (None, "heads", "embed"),
        "mlp_norm": (None, "embed"),
        "router": (None, "embed", None),          # router stays replicated
        "wi_gate": (None, "expert", "embed", "mlp"),
        "wi_up": (None, "expert", "embed", "mlp"),
        "wo_e": (None, "expert", "mlp", "embed"),
    }
    axes = {
        "embedding": ("vocab", "embed"),
        "blocks": block,
        "final_norm": ("embed",),
    }
    if not cfg.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


def init_params(cfg: OlmoeConfig, key) -> dict:
    """The parameter pytree (stacked-block layout; the router in float32)."""
    dt = cfg.param_dtype
    k_emb, k_blocks, k_head = jax.random.split(key, 3)
    d, l, e, f = cfg.d_model, cfg.n_layers, cfg.n_experts, cfg.d_ff
    qdim = cfg.n_heads * cfg.head_dim
    kvdim = cfg.n_kv_heads * cfg.head_dim

    def dense(key, shape, fan_in, dtype=dt):
        return fanin_init(key, shape, fan_in).astype(dtype)

    ks = jax.random.split(k_blocks, 8)
    blocks = {
        "attn_norm": jnp.ones((l, d), dtype=dt),
        "wq": dense(ks[0], (l, d, qdim), d),
        "wk": dense(ks[1], (l, d, kvdim), d),
        "wv": dense(ks[2], (l, d, kvdim), d),
        "q_norm": jnp.ones((l, qdim), dtype=dt),
        "k_norm": jnp.ones((l, kvdim), dtype=dt),
        "wo": dense(ks[3], (l, qdim, d), qdim),
        "mlp_norm": jnp.ones((l, d), dtype=dt),
        "router": dense(ks[4], (l, d, e), d, dtype=jnp.float32),
        "wi_gate": dense(ks[5], (l, e, d, f), d),
        "wi_up": dense(ks[6], (l, e, d, f), d),
        "wo_e": dense(ks[7], (l, e, f, d), f),
    }
    params = {
        "embedding": dense(k_emb, (cfg.vocab_size, d), d),
        "blocks": blocks,
        "final_norm": jnp.ones((d,), dtype=dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense(k_head, (d, cfg.vocab_size), d)
    return params


# ---------------------------------------------------------------------------
# The block, as two pieces
# ---------------------------------------------------------------------------

def attention_projections(cfg: OlmoeConfig, p, x, sin, cos):
    """What attention takes in, from the residual stream ``x`` [b, s, d]:
    pre-norm, the three projections, RMSNorm of ``q`` and of ``k`` over
    the whole projection, the split into heads, rotary on ``q`` and ``k``.
    Returns (q [b, s, heads, hd], k, v [b, s, kv heads, hd])."""
    b, s, _ = x.shape
    with jax.named_scope(scopes.ATTN_QKV):
        h = rms_norm(x, p["attn_norm"], eps=cfg.rms_eps)
        q = rms_norm(h @ p["wq"], p["q_norm"], eps=cfg.rms_eps)
        k = rms_norm(h @ p["wk"], p["k_norm"], eps=cfg.rms_eps)
        q = q.reshape(b, s, cfg.n_heads, cfg.head_dim)
        k = k.reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
        v = (h @ p["wv"]).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
        return apply_rope(q, sin, cos), apply_rope(k, sin, cos), v


def feed_forward(cfg: OlmoeConfig, p, x, valid=None, stacked=None):
    """Pre-norm routed feed-forward over ``x`` [b, s, d]; returns (the
    residual-added stream, its statistics). ``valid`` [b, s] marks the rows
    that are tokens: padding is sent to no expert and counts in no
    statistic. The statistics are scalars of this call: how many experts
    got a token, and the busiest expert's load over the mean load.
    ``stacked``: (the run's weights stacked on their layer axis, this
    layer's index in them), from a program that scans the run: the expert
    stacks are then read from there in place (``moe_ffn_dropless``'s
    ``layer``), not from ``p``'s slices."""
    b, s, d = x.shape
    h = rms_norm(x, p["mlp_norm"], eps=cfg.rms_eps)
    held, layer = (p, None) if stacked is None else stacked
    out, load = moe_ffn_dropless(
        h.reshape(b * s, d), p["router"], held["wi_gate"], held["wi_up"],
        held["wo_e"], layer=layer, top_k=cfg.top_k,
        norm_topk_prob=cfg.norm_topk_prob,
        valid=None if valid is None else valid.reshape(b * s))
    with jax.named_scope(scopes.MOE_ROUTER):
        load = load.astype(jnp.float32)
        stats = {
            "experts_touched": jnp.sum(load > 0, dtype=jnp.float32),
            "expert_load_max_over_mean":
                jnp.max(load) / jnp.maximum(jnp.mean(load), 1e-9),
        }
    with jax.named_scope(scopes.MOE_COMBINE):
        return x + out.reshape(b, s, d), stats


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def forward(cfg: OlmoeConfig, params: dict, tokens, *,
            attn_impl: str = "auto"):
    """Token ids [batch, seq] -> logits [batch, seq, vocab] (fp32)."""
    b, s = tokens.shape
    x = params["embedding"][tokens]
    positions = jnp.arange(s, dtype=jnp.int32)[None, :]
    sin, cos = rope_sin_cos(positions, cfg.head_dim, theta=cfg.rope_theta)

    def block(x, p):
        q, k, v = attention_projections(cfg, p, x, sin, cos)
        attn = attention(q, k, v, causal=True, impl=attn_impl)
        x = attention_output(cfg, p, x, attn)
        x, _ = feed_forward(cfg, p, x)
        return x, None

    x, _ = lax.scan(block, x, params["blocks"])
    x = rms_norm(x, params["final_norm"], eps=cfg.rms_eps)
    return jnp.einsum("bsd,dv->bsv", x, lm_head_weights(cfg, params),
                      preferred_element_type=jnp.float32)
