"""dots3-note-family decoder (dots-studio dots3-note-prev, ``model_type``
``dots3_note``; the text path of the language model), for serving.

Every layer attends through a LATENT: a token keeps one compressed row
(``ckv | kr``: the normed, rescaled KV latent beside one rotary key all
heads share) and no keys or values (``ops/latent_attention.py``). The
layers are of two shapes (``layer_types``):

- a ``full_attention`` layer: ``n_heads`` heads over a latent of
  ``kv_rank`` (576 numbers a token as published), rotary base
  ``rope_theta``, and an INDEXER: ``index_heads`` small heads score every
  key a query may see, from an index key each token keeps beside its row,
  and the layer's softmax runs over the ``index_topk`` best alone;
- a ``sliding_attention`` layer: ``n_heads_sliding`` heads over a latent
  of ``kv_rank_sliding`` with wider no-position keys (1,088 numbers a
  token), rotary base ``rope_theta_sliding``, the ``window`` newest keys.

Both: the query goes through a latent of its own (``q_rank``), both
latents are normed and then rescaled by ``sqrt(d_model / rank)`` (``rescale``:
``apply_mla_qkv_lora_rescale``), and each head's output is gated by
``sigmoid(u @ wg)`` of the sublayer's normed input before ``wo``.

The feed-forward of the layers in ``mlp_only_layers`` is a dense SwiGLU;
every other layer's is ``n_experts`` routed experts, ``top_k`` a token,
scored by SIGMOID, chosen by ``score + router_bias`` (a per-expert
correction that moves the choice and no weight: ``topk_method``
``noaux_tc``), the chosen scores renormalised, plus one shared expert on
every token. Of the routed experts this process may hold a share
(``n_experts_held`` from ``first_expert``), as ``models/laguna.py``.

``params["blocks"]`` maps a run's key to the weights of that run of
identical consecutive layers, stacked on a leading axis; ``layer_plan``
lists the runs with the PAGE ROWS each keeps a token. Everything a layer
projects from its normed input is ONE stack ``w_in`` (columns query
latent | KV latent | rotary key | gate, then index key | index weights in
a full layer): five matmuls of one input as one, and no stack small
enough for the compiler to park on the core (``models/llama.py:
fuse_attention_projections``).

The block's pieces take no view on where rows live (``latent_projections``,
``attention_output``, ``feed_forward``): ``forward`` attends over the
prompt's own rows in the expanded form, the paged serving engine over its
pools. No training path.
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
from ray_tpu.ops.latent_attention import (IndexInputs, LatentInputs,
                                          latent_decode_attention,
                                          latent_prefill_attention,
                                          write_latent)
from ray_tpu.ops.moe import moe_ffn_dropless, share_statistics
from ray_tpu.ops.norms import layer_norm, rms_norm
from ray_tpu.ops.paged_attention import PageRow, row_pool
from ray_tpu.ops.rope import apply_rope, rope_sin_cos

_PERIOD = ("full_attention", "sliding_attention", "sliding_attention",
           "sliding_attention")


@dataclass(frozen=True)
class Dots3NoteConfig:
    vocab_size: int = 152064
    d_model: int = 5120
    layer_types: tuple = ("full_attention",) + _PERIOD * 11 + (
        "full_attention",)
    mlp_only_layers: tuple = (0,)
    q_rank: int = 1024                # both shapes' query latent
    rope_dim: int = 64                # rotary numbers a head, both shapes
    v_dim: int = 128                  # a head's value, both shapes
    n_heads: int = 128                # a full layer
    kv_rank: int = 512
    nope_dim: int = 128
    rope_theta: float = 8e7
    index_heads: int = 64             # its indexer
    index_dim: int = 128
    index_topk: int = 2048
    n_heads_sliding: int = 64         # a sliding layer
    kv_rank_sliding: int = 1024
    nope_dim_sliding: int = 192
    rope_theta_sliding: float = 50000.0
    window: int = 513                 # keys a query sees, its own among them
    rescale: bool = True              # latents times sqrt(d_model / rank)
    d_ff: int = 13824                 # a dense layer's width
    d_expert: int = 1536              # one routed expert's
    d_shared: int = 1536              # the shared expert's
    n_experts: int = 256              # the router's width
    n_experts_held: int = 256         # experts whose weights are here,
    first_expert: int = 0             # from this one
    top_k: int = 8
    norm_topk_prob: bool = True
    routed_scale: float = 1.0
    rms_eps: float = 1e-5
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

    def shape(self, sliding: bool) -> tuple:
        """(heads, KV rank, no-position width) of a layer's attention."""
        if sliding:
            return (self.n_heads_sliding, self.kv_rank_sliding,
                    self.nope_dim_sliding)
        return self.n_heads, self.kv_rank, self.nope_dim


def dots3_note_prev() -> Dots3NoteConfig:
    """dots3-note-prev as published: 46 layers, every expert held."""
    return Dots3NoteConfig()


def dots3_note_tiny(vocab_size: int = 128, **changes) -> Dots3NoteConfig:
    """Test-size config in float32: the leading dense layer and one period
    (full, full, sliding x 3), 2 of 8 experts held, a selection of 12 keys
    and a window of 9."""
    kw = dict(
        vocab_size=vocab_size, d_model=64,
        layer_types=("full_attention",) + _PERIOD,
        q_rank=32, rope_dim=8, v_dim=16, n_heads=4, kv_rank=16,
        nope_dim=16, index_heads=2, index_dim=16, index_topk=12,
        n_heads_sliding=2, kv_rank_sliding=32, nope_dim_sliding=24,
        window=9, d_ff=128, d_expert=32, d_shared=32, n_experts=8,
        n_experts_held=2, top_k=3, dtype="float32")
    kw.update(changes)
    return Dots3NoteConfig(**kw)


# ---------------------------------------------------------------------------
# The layer plan
# ---------------------------------------------------------------------------

def _runs(cfg: Dots3NoteConfig) -> list:
    """Runs of consecutive layers of one attention shape and one kind of
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


def page_rows(cfg: Dots3NoteConfig, sliding: bool) -> tuple:
    """What a token keeps in a page of a layer of this shape: its latent
    row and, in a full layer, its index key."""
    _, rank, _ = cfg.shape(sliding)
    latent = PageRow("latent", rank + cfg.rope_dim, cfg.dtype)
    if sliding:
        return (latent,)
    return latent, PageRow("index_key", cfg.index_dim, cfg.dtype)


def layer_plan(cfg: Dots3NoteConfig) -> tuple:
    """The runs of identical layers, in order, as the serving engine's
    layer loop takes them: each run's key in ``params["blocks"]``, the
    kind of its attention (which rotary table), its window or none, its
    length, the rows a token keeps in its pages and, for a layer with an
    indexer, how many keys a query attends over."""
    return tuple(
        LayerStack(key, "sliding" if sliding else "full",
                   cfg.window if sliding else None, n,
                   rows=page_rows(cfg, sliding),
                   selects=None if sliding else cfg.index_topk)
        for key, sliding, _, n in _runs(cfg))


def rotary_tables(cfg: Dots3NoteConfig, positions) -> dict:
    """(sin, cos) of ``positions`` over a head's ``rope_dim`` rotary
    numbers, for each kind of layer (a full layer's indexer takes the
    full layer's)."""
    return {"full": rope_sin_cos(positions, cfg.rope_dim,
                                 theta=cfg.rope_theta),
            "sliding": rope_sin_cos(positions, cfg.rope_dim,
                                    theta=cfg.rope_theta_sliding)}


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

# Seeded weights that a bf16 program and a float32 reference can AGREE on
# and on which every mechanism of the block still SHOWS in the logits
# (models/laguna.py:init_params has the argument for a router; the
# numbers are a v5e's at the cell's widths, 64 logit rows behind 2,560
# tokens, my chip runs, PR 40; the configuration's file repeats them).
# With every matrix at the fan-in scale the rescaled latents (times sqrt 5
# and sqrt 10 after their norms) give attention scores a standard
# deviation of 6: every head attends to one key, and the indexer's choice
# of 2,048 of 2,600 keys, which a bf16 stream's rounding tips for a
# handful of keys a query, then tips whole heads: the bf16 program read
# 0.20 rms from the float32 reference on logits of unit variance, 25 of 64
# tokens not the reference's own, the worst 1.09 short (0.075 rms with the
# selection off on both sides, 0.007 with the rescale off). A trained
# model's expansions have learned the rescale. So:
# - the query's expansion ``wq_b`` is at ``_QUERY_GAIN`` of the fan-in
#   scale: scores of deviation about 1.5 in a full layer, some hundreds of
#   keys a head. What the selection's tipping moves and what a reference
#   WITHOUT the indexer moves both go with the full layers' part of the
#   logits, about 1 to 9 in the mean where the selection drops a fifth of
#   the keys (at 0.4 the program read 0.030 rms and the blind reference
#   0.24; at 0.25, 0.013 and 0.076) and further apart where it drops
#   half, which is why the cell's check runs at 4,000 tokens. The check
#   reads each side's WORST token of 64, and the two meet at their tails
#   (sixteen seeds of the check on a v5e, the engine's tokens short of the
#   reference's best and the same tokens against the reference blind to
#   the indexer, my chip runs, PR 58, ``scripts/note_check_seeds.py``):
#   at 0.25 the program read 0.005-0.158, over the check's 0.1 on three
#   seeds through the plain prefill and on two through the kernel's (4
#   and 3 of forty), and the blind reference 0.28-0.54 (``topk=1024``
#   0.25-0.61); at 0.225, 0.000-0.101 (one seed over) and 0.15-0.46; at
#   0.2, 0.000-0.099 over forty seeds, none over the limit, but the
#   blind reference 0.09-0.39: one seed of sixteen would pass a program
#   blind to its indexer. So 0.25 stays: a seed in thirteen refuses a
#   correct program for a tipping (PERF.md section 7), none passes a
#   blind one; a smaller scale trades the one for the other, and the way
#   out is the check's (an rms over positions, not the worst of 64);
# - a sigmoid router's chosen experts weigh about alike whatever its
#   scale, so a choice that tips between the eighth and the ninth moves a
#   whole expert's part, as the correction bias's own changes do: the
#   routed experts' output projections are at ``_EXPERT_OUT_GAIN`` of the
#   fan-in scale, where one expert more or less moves a logit by a
#   hundredth and the bias, which moves several choices of EVERY token in
#   every sparse layer, by 0.09-0.10 rms (its tokens 0.23-0.26 short); the
#   bias is N(0, ``_ROUTER_BIAS_STD``^2), which reorders most tokens'
#   choices (the chosen sigmoids lie within hundredths of each other: 0.3
#   moved nothing more);
# - the router is at four times the fan-in scale, so that a softmax over
#   its logits (the other reading of ``scoring_func``) weighs the chosen
#   experts quite unlike the sigmoid (0.18 rms);
# - the embedding's rows have unit variance; everything else is at the
#   fan-in scale, the feed-forwards' output projections at 1/sqrt(2 x
#   layers) of it.
_EMBEDDING_STD = 1.0
_INDEX_NORM_EPS = 1e-6      # the index key's LayerNorm (torch's default)
_QUERY_GAIN = 0.25
_ROUTER_GAIN = 4.0
_ROUTER_BIAS_STD = 0.1
_EXPERT_OUT_GAIN = 0.6


def init_params(cfg: Dots3NoteConfig, key) -> dict:
    """The parameter pytree: ``blocks`` maps each run's key to its stacked
    weights (the router and its correction bias in float32). Scales: the
    note above."""
    dt = cfg.param_dtype
    d, rq = cfg.d_model, cfg.q_rank
    branches = 2 * cfg.n_layers          # residual branches in the stack

    def dense(key, shape, fan_in, dtype=dt, gain=1.0):
        return (fanin_init(key, shape, fan_in) * gain).astype(dtype)

    runs = _runs(cfg)
    k_emb, k_head, *k_runs = jax.random.split(key, 2 + len(runs))
    blocks = {}
    for (name, sliding, is_dense, n), k_run in zip(runs, k_runs):
        heads, rank, nope = cfg.shape(sliding)
        ks = jax.random.split(k_run, 13)
        w_in = rq + rank + cfg.rope_dim + heads
        if not sliding:
            w_in += cfg.index_dim + cfg.index_heads
        p = {
            "attn_norm": jnp.ones((n, d), dtype=dt),
            "w_in": dense(ks[0], (n, d, w_in), d),
            "q_norm": jnp.ones((n, rq), dtype=dt),
            "wq_b": dense(ks[1], (n, rq, heads * (nope + cfg.rope_dim)), rq,
                          gain=_QUERY_GAIN),
            "kv_norm": jnp.ones((n, rank), dtype=dt),
            "wkv_b": dense(ks[2], (n, rank, heads * (nope + cfg.v_dim)),
                           rank),
            "wo": dense(ks[3], (n, heads * cfg.v_dim, d),
                        heads * cfg.v_dim),
            "mlp_norm": jnp.ones((n, d), dtype=dt),
        }
        if not sliding:
            p.update(
                wi_q=dense(ks[4], (n, rq, cfg.index_heads * cfg.index_dim),
                           rq),
                index_norm=jnp.ones((n, cfg.index_dim), dtype=dt),
                index_norm_bias=jnp.zeros((n, cfg.index_dim), dtype=dt))
        if is_dense:
            p.update(w_gate=dense(ks[5], (n, d, cfg.d_ff), d),
                     w_up=dense(ks[6], (n, d, cfg.d_ff), d),
                     w_down=dense(ks[7], (n, cfg.d_ff, d),
                                  cfg.d_ff * branches))
        else:
            e, f, fs = cfg.n_experts_held, cfg.d_expert, cfg.d_shared
            p.update(
                router=dense(ks[5], (n, d, cfg.n_experts), d,
                             dtype=jnp.float32, gain=_ROUTER_GAIN),
                router_bias=_ROUTER_BIAS_STD * jax.random.normal(
                    ks[12], (n, cfg.n_experts), jnp.float32),
                wi_gate=dense(ks[6], (n, e, d, f), d),
                wi_up=dense(ks[7], (n, e, d, f), d),
                wo_e=dense(ks[8], (n, e, f, d), f, gain=_EXPERT_OUT_GAIN),
                ws_gate=dense(ks[9], (n, d, fs), d),
                ws_up=dense(ks[10], (n, d, fs), d),
                ws_down=dense(ks[11], (n, fs, d), fs * branches))
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

def _rotate_leading(x, sin, cos):
    """Rotary on the leading ``2 x sin.shape[-1]`` numbers of each head of
    ``x`` [b, s, heads, width]; the rest pass through."""
    rot = 2 * sin.shape[-1]
    with jax.named_scope(scopes.ATTN_QKV):
        return jnp.concatenate(
            [apply_rope(x[..., :rot], sin, cos), x[..., rot:]], axis=-1)


def _projected(cfg: Dots3NoteConfig, p, x):
    """(the normed input's projections through ``w_in`` [b, s, ...] in
    float32, whether the layer is a sliding one: its stack says)."""
    h = rms_norm(x, p["attn_norm"], eps=cfg.rms_eps)
    sliding = "wi_q" not in p
    return jnp.einsum("bsd,de->bse", h, p["w_in"],
                      preferred_element_type=jnp.float32), sliding


def latent_projections(cfg: Dots3NoteConfig, p, x, sin, cos) -> LatentInputs:
    """What the layer's attention takes in, from the residual stream ``x``
    [b, s, d]: pre-norm; the query through its latent (normed, rescaled)
    into heads, its last ``rope_dim`` numbers rotated; the token's row:
    the KV latent (normed, rescaled) beside the rotated shared key; the
    expansion ``wkv_b`` by head; and, in a full layer, the indexer's
    queries (from the query latent), head weights and the token's index
    key (layer-normed), both rotated over their first ``rope_dim``."""
    b, s, d = x.shape
    with jax.named_scope(scopes.ATTN_QKV):
        y, sliding = _projected(cfg, p, x)
        heads, rank, nope = cfg.shape(sliding)
        rq, dr, dt = cfg.q_rank, cfg.rope_dim, x.dtype
        cq = rms_norm(y[..., :rq], p["q_norm"], eps=cfg.rms_eps)
        ckv = rms_norm(y[..., rq:rq + rank], p["kv_norm"], eps=cfg.rms_eps)
        if cfg.rescale:
            cq = cq * (d / rq) ** 0.5
            ckv = ckv * (d / rank) ** 0.5
        cq, ckv = cq.astype(dt), ckv.astype(dt)
        q = (cq @ p["wq_b"]).reshape(b, s, heads, nope + dr)
        q = jnp.concatenate(
            [q[..., :nope], apply_rope(q[..., nope:], sin, cos)], axis=-1)
        at = rq + rank
        kr = apply_rope(y[..., None, at:at + dr], sin, cos)[:, :, 0]
        index = None
        if not sliding:
            at += dr + heads
            qi = (cq @ p["wi_q"]).reshape(b, s, cfg.index_heads, cfg.index_dim)
            ki = layer_norm(y[..., at:at + cfg.index_dim], p["index_norm"],
                            p["index_norm_bias"], eps=_INDEX_NORM_EPS)
            index = IndexInputs(
                _rotate_leading(qi, sin, cos),
                y[..., at + cfg.index_dim:],
                _rotate_leading(ki[..., None, :], sin, cos)[:, :, 0].astype(
                    dt),
                cfg.index_topk)
        return LatentInputs(
            q, jnp.concatenate([ckv, kr.astype(dt)], axis=-1),
            p["wkv_b"].reshape(rank, heads, nope + cfg.v_dim),
            (nope + dr) ** -0.5, index)


def attention_output(cfg: Dots3NoteConfig, p, x, attn):
    """The attention sublayer's end: each head's output ``attn`` ([b, s,
    heads, dv], or [b, heads, dv] of a one-token step) times the head's
    gate, ``sigmoid(u @ wg)`` of the sublayer's normed input, then ``wo``,
    added to ``x`` [b, s, d]. (The projection through ``w_in`` is the one
    ``latent_projections`` took: the compiler computes it once.)"""
    b, s, _ = x.shape
    with jax.named_scope(scopes.ATTN_OUT):
        y, sliding = _projected(cfg, p, x)
        heads, rank, _ = cfg.shape(sliding)
        at = cfg.q_rank + rank + cfg.rope_dim
        gate = jax.nn.sigmoid(y[..., at:at + heads])         # [b, s, heads]
        attn = attn.reshape(b, s, heads, cfg.v_dim) * gate[..., None]
        return x + attn.astype(x.dtype).reshape(b, s, -1) @ p["wo"]


def feed_forward(cfg: Dots3NoteConfig, p, x, valid=None, stacked=None):
    """Pre-norm feed-forward over ``x`` [b, s, d]; returns (the
    residual-added stream, its statistics). A dense layer (its weights say
    which) is a SwiGLU with no statistics. Any other: the held routed
    experts' part for the tokens routed to them (sigmoid scores, the choice
    by score plus the correction bias), plus the shared expert on every
    token; statistics over the HELD experts, as
    ``models/laguna.py:feed_forward``'s. ``stacked``: (the run's weights
    stacked on their layer axis, this layer's index in them), from a
    program that scans the run: the expert stacks are then read from there
    in place (``moe_ffn_dropless``'s ``layer``), not from ``p``'s slices."""
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
        valid=None if valid is None else valid.reshape(b * s),
        scoring="sigmoid", choice_bias=p["router_bias"])
    with jax.named_scope(scopes.SHARED_EXPERT):
        shared = (jax.nn.silu(h @ p["ws_gate"])
                  * (h @ p["ws_up"])) @ p["ws_down"]
    stats = share_statistics(load, valid, b * s, cfg.top_k)
    with jax.named_scope(scopes.MOE_COMBINE):
        return x + routed.reshape(b, s, d) + shared, stats


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def forward(cfg: Dots3NoteConfig, params: dict, tokens, *,
            absorbed: bool = False):
    """Token ids [batch, seq] -> logits [batch, seq, vocab] (fp32): the
    plain causal path, the runs of the layer plan one after another, each
    layer attending over the rows its own tokens keep (a one-page pool of
    this prompt alone) in the expanded form, or with ``absorbed`` one
    query at a time in the absorbed form: the same numbers."""
    b, s = tokens.shape
    x = params["embedding"][tokens]
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    tables = rotary_tables(cfg, positions)
    start = jnp.zeros((b,), jnp.int32)
    # every sequence's rows in a page of its own, s tokens long
    table = jnp.arange(b, dtype=jnp.int32)[:, None]
    slot, offset = jnp.broadcast_to(table, (b, s)), positions
    for run in layer_plan(cfg):
        sin, cos = tables[run.kind]

        def block(x, p, run=run, sin=sin, cos=cos):
            inputs = latent_projections(cfg, p, x, sin, cos)
            pools = write_latent(inputs, tuple(
                row_pool(1, b, s, row) for row in run.rows), 0, slot,
                offset)
            if absorbed:
                def one(t):
                    def at(a):
                        return a[:, t][:, None]
                    index = inputs.index and inputs.index._replace(
                        q=at(inputs.index.q), key=at(inputs.index.key),
                        weights=at(inputs.index.weights))
                    step = inputs._replace(q=at(inputs.q),
                                           row=at(inputs.row), index=index)
                    return latent_decode_attention(
                        step, pools, 0, table, start + t, window=run.window)
                attn = jnp.moveaxis(lax.map(one, jnp.arange(s)), 0, 1)
            else:
                attn = latent_prefill_attention(
                    inputs, pools, 0, table, start, jnp.full_like(start, s),
                    window=run.window)
            x = attention_output(cfg, p, x, attn)
            x, _ = feed_forward(cfg, p, x)
            return x, None

        x, _ = lax.scan(block, x, params["blocks"][run.key])
    x = rms_norm(x, params["final_norm"], eps=cfg.rms_eps)
    return jnp.einsum("bsd,dv->bsv", x, lm_head_weights(cfg, params),
                      preferred_element_type=jnp.float32)
