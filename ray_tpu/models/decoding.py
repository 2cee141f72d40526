"""The library's plain decode: KV cache, prefill/decode, sampling, generation.

Net-new capability vs the reference, which serves models only through user
code inside Serve replicas (`python/ray/serve/`, P15). This module is the
straightforward formulation: offline/eval generation (`generate`), the
substrate of `ray_tpu.models.speculative`, and the ORACLE the tests hold
the serving engine (`ray_tpu.serve.paged_llm`) to. The engine does not
run it: its programs keep a paged KV pool (`ray_tpu.ops.paged_attention`)
and share with this module only `select_tokens` and
`ops.attention.cached_attention`.

- One **unified cached forward** handles prefill (T=prompt) and decode (T=1):
  static shapes, per-sequence write offsets via vmapped dynamic slicing.
- The KV cache is contiguous rows: `[layers, batch, max_len, kv_heads, hd]`,
  one row a sequence. Which rows are live is the caller's bookkeeping,
  expressed as masks, never Python branches.
- Layers run under `lax.scan` with the cache as scanned xs/ys, matching the
  stacked-block layout of `ray_tpu.models.llama`.
- Sampling (greedy/temperature/top-k/top-p) is jitted alongside the model
  so logits never leave HBM.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models import llama
from ray_tpu.ops import scopes
from ray_tpu.ops.attention import cached_attention
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.rope import apply_rope, rope_sin_cos


@jax.tree_util.register_pytree_node_class
@dataclass
class KVCache:
    """Slot-based KV cache.

    k, v: [n_layers, max_batch, max_len, n_kv_heads, head_dim]
    lengths: [max_batch] int32 — tokens currently cached per slot.
    """

    k: jax.Array
    v: jax.Array
    lengths: jax.Array

    def tree_flatten(self):
        return (self.k, self.v, self.lengths), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def max_batch(self) -> int:
        return self.k.shape[1]

    @property
    def max_len(self) -> int:
        return self.k.shape[2]


def init_cache(cfg, max_batch: int, max_len: int, dtype=None) -> KVCache:
    dtype = dtype or cfg.param_dtype
    shape = (cfg.n_layers, max_batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    return KVCache(
        k=jnp.zeros(shape, dtype=dtype),
        v=jnp.zeros(shape, dtype=dtype),
        lengths=jnp.zeros((max_batch,), dtype=jnp.int32),
    )


def _write_cache(cache_kv, new_kv, start):
    """Write new_kv [B, T, ...] into cache_kv [B, S, ...] at per-row offsets
    start [B]. vmapped dynamic_update_slice keeps shapes static."""

    def write_one(row_cache, row_new, s):
        return lax.dynamic_update_slice(
            row_cache, row_new.astype(row_cache.dtype), (s, 0, 0)
        )

    return jax.vmap(write_one)(cache_kv, new_kv, start)


def cached_forward(cfg, params, tokens, cache: KVCache, *,
                   start=None, logits_mode: str = "last", logits_idx=None):
    """Run the transformer over `tokens` [B, T] against/through the cache.

    start [B]: write offset per row (defaults to cache.lengths). The cache
    rows are updated in place (functionally); `cache.lengths` is NOT
    advanced here — the caller owns slot bookkeeping (so speculative or
    masked steps stay possible).

    Returns (logits, new_cache); logits_mode:
      "last"  -> [B, vocab] at position T-1 (decode steps)
      "index" -> [B, vocab] at per-row position logits_idx [B] (prefill of
                 right-padded prompts: idx = prompt_len - 1). Keeps memory
                 at O(d_model), not O(vocab*T).
      "all"   -> [B, T, vocab]

    Reference analog: none — the reference delegates model execution to
    user frameworks inside replicas (SURVEY.md P15); this is the TPU-native
    serving compute path.
    """
    b, t = tokens.shape
    if start is None:
        start = cache.lengths
    x = params["embedding"][tokens]
    positions = start[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    sin, cos = rope_sin_cos(positions, cfg.head_dim, theta=cfg.rope_theta)
    scale = cfg.head_dim ** -0.5

    def block(x, xs):
        p, k_cache, v_cache = xs
        h = rms_norm(x, p["attn_norm"], eps=cfg.rms_eps)
        q = (h @ p["wq"]).reshape(b, t, cfg.n_heads, cfg.head_dim)
        k = (h @ p["wk"]).reshape(b, t, cfg.n_kv_heads, cfg.head_dim)
        v = (h @ p["wv"]).reshape(b, t, cfg.n_kv_heads, cfg.head_dim)
        q = apply_rope(q, sin, cos)
        k = apply_rope(k, sin, cos)
        k_cache = _write_cache(k_cache, k, start)
        v_cache = _write_cache(v_cache, v, start)
        attn = cached_attention(q, k_cache, v_cache, start, scale=scale)
        x = x + attn.reshape(b, t, cfg.n_heads * cfg.head_dim) @ p["wo"]
        h = rms_norm(x, p["mlp_norm"], eps=cfg.rms_eps)
        gated = jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])
        x = x + gated @ p["w_down"]
        return x, (k_cache, v_cache)

    x, (new_k, new_v) = lax.scan(
        block, x, (params["blocks"], cache.k, cache.v)
    )
    x = rms_norm(x, params["final_norm"], eps=cfg.rms_eps)
    head = llama.lm_head_weights(cfg, params)
    if logits_mode == "last":
        x = x[:, -1, :]
    elif logits_mode == "index":
        x = jnp.take_along_axis(x, logits_idx[:, None, None], axis=1)
        x = x.squeeze(1)
    if logits_mode in ("last", "index"):
        logits = jnp.einsum("bd,dv->bv", x, head,
                            preferred_element_type=jnp.float32)
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, head,
                            preferred_element_type=jnp.float32)
    return logits, KVCache(k=new_k, v=new_v, lengths=cache.lengths)


# ---------------------------------------------------------------------------
# Sampling
# ---------------------------------------------------------------------------

def select_tokens(logits, temps, key):
    """The serving engine's per-slot token choice: greedy at temp 0,
    temperature-scaled categorical otherwise. ONE implementation: the
    engine's decode and prefill programs both call it."""
    with jax.named_scope(scopes.SAMPLE):
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
        sampled = jax.random.categorical(key, scaled, axis=-1).astype(
            jnp.int32)
        return jnp.where(temps > 0.0, sampled, greedy)


@dataclass(frozen=True)
class SamplingParams:
    temperature: float = 1.0
    top_k: int = 0          # 0 = disabled
    top_p: float = 1.0      # 1.0 = disabled
    max_new_tokens: int = 128


def sample(logits, key, params: SamplingParams):
    """logits [B, V] -> token ids [B]. temperature==0 means greedy."""
    if params.temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / jnp.maximum(params.temperature, 1e-6)
    if params.top_k > 0:
        kth = jnp.sort(logits, axis=-1)[:, -params.top_k][:, None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if params.top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep the smallest prefix with mass >= top_p (always >= 1 token)
        cutoff_idx = jnp.sum(cum < params.top_p, axis=-1)
        cutoff = jnp.take_along_axis(
            sorted_logits, cutoff_idx[:, None], axis=-1
        )
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Whole-batch generation (offline / eval path)
# ---------------------------------------------------------------------------

def generate(cfg, params, prompts, *, key=None,
             sampling: SamplingParams | None = None,
             eos_id: int | None = None, pad_id: int = 0):
    """Batch generation: prompts [B, P] (right-padded with pad_id; actual
    lengths inferred), returns tokens [B, max_new_tokens] (pad_id after eos).

    Everything after prefill is one `lax.scan` — the whole decode loop is a
    single XLA program.
    """
    sampling = sampling or SamplingParams()
    key = key if key is not None else jax.random.key(0)
    b, p = prompts.shape
    # length = 1 + last non-pad POSITION (not a count): a valid interior
    # token equal to pad_id must not shorten the prompt
    positions = jnp.arange(p, dtype=jnp.int32)[None, :]
    prompt_lens = jnp.max(
        jnp.where(prompts != pad_id, positions + 1, 0), axis=1)
    prompt_lens = jnp.maximum(prompt_lens, 1)
    max_len = p + sampling.max_new_tokens
    cache = init_cache(cfg, b, max_len)

    # logits at position len-1 predict the first new token
    last, cache = cached_forward(
        cfg, params, prompts, cache, start=jnp.zeros((b,), jnp.int32),
        logits_mode="index", logits_idx=prompt_lens - 1,
    )
    key, sub = jax.random.split(key)
    first = sample(last, sub, sampling)
    cache = KVCache(k=cache.k, v=cache.v, lengths=prompt_lens)

    def step(carry, key_t):
        cache, tok, done = carry
        logits, cache = cached_forward(
            cfg, params, tok[:, None], cache, logits_mode="last"
        )
        nxt = sample(logits, key_t, sampling)
        nxt = jnp.where(done, pad_id, nxt)
        if eos_id is not None:
            done = done | (nxt == eos_id)
        cache = KVCache(k=cache.k, v=cache.v, lengths=cache.lengths + 1)
        return (cache, nxt, done), nxt

    done0 = (first == eos_id) if eos_id is not None else jnp.zeros((b,), bool)
    keys = jax.random.split(key, max(sampling.max_new_tokens - 1, 1))
    (_, _, _), rest = lax.scan(step, (cache, first, done0), keys[: sampling.max_new_tokens - 1])
    out = jnp.concatenate([first[:, None], rest.T], axis=1)
    return out


generate_jit = jax.jit(
    generate, static_argnums=(0,), static_argnames=("sampling", "eos_id", "pad_id")
)
