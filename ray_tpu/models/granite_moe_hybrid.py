"""Granite-4.0-H-family hybrid decoder (IBM Granite 4.0-H, ``model_type``
``granitemoehybrid``), for serving.

Every layer is TWO sublayers, each under its own pre-norm and the same
fixed ``residual_multiplier`` ``m``: a sequence mixer, the entry of
``layer_types`` saying which, AND THEN routed experts beside a shared one:

    h <- h + m * Mix_i(rms(h, norm_i))
    h <- h + m * (Routed_i(g) + Shared_i(g)),   g = rms(h, ffn_norm_i)

- ``mamba``, a Mamba-2 mixer: Falcon-H1's mixer without its multipliers
  (``models/falcon_h1.py`` has the equations and, between this module's
  two ends of it, the code: ``recurrent_mixer``, ``recurrent_step``,
  ``ops/ssm.py``), ONE group (every head reads the same B and C, and the
  gated norm runs over the whole inner width). A sequence keeps the
  float32 state ``S`` [H, P, N] and the convolution's tail in such a
  layer, and no page;
- ``attention``: grouped-query, causal, q | k | v from one stack
  ``wqkv``, NO rotary embedding (the mixers carry position), and scores
  scaled by ``attention_multiplier``, which is NOT ``head_dim ** -0.5``.
  The engine's page attention takes no scale but its own
  (``ops/paged_attention.py:page_attention_scale``), so the ratio of the
  two is folded into q, in float32, BEFORE q's one rounding to the
  model's dtype (``attention_projections``), as Falcon-H1 folds
  ``key_multiplier`` into k. A sequence keeps K/V pages in such a layer
  and nothing else;
- the routed experts: float32 logits over ``n_experts``, the ``top_k``
  largest, their weights a softmax over the chosen logits alone (a
  softmax over all of them renormalised over the chosen is the same
  numbers: ``ops.moe.moe_ffn_dropless`` with ``norm_topk_prob``), each
  expert a SwiGLU; plus one shared SwiGLU expert on every token. Of the
  routed experts this process may hold a share (``n_experts_held`` from
  ``first_expert``), as Laguna's does.

The stream starts as ``embedding[ids] * embedding_multiplier`` and ends
in a head TIED to the embedding, its logits divided by
``logits_scaling``; the head contracts over the embedding where it lies
([vocab, d], its own layout: ``head_logits``), no transpose of it is
built.

``layer_plan`` says what each run of consecutive layers of one type holds
and does (``LayerStack.attends``, ``state``, ``feeds``: every run feeds),
and the serving engine's stores have the layers that keep them: K/V
pools over the attention layers, state arrays over the mixer layers.
``params["blocks"]`` maps a run's key to its weights stacked on a leading
axis. The pieces carry ``jax.named_scope``s (``ops/scopes.py``). No
training path: there are no logical axes and no loss here.
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
from ray_tpu.ops.moe import moe_ffn_dropless, share_statistics
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.paged_attention import page_attention_scale

_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4


@dataclass(frozen=True)
class GraniteMoeHybridConfig:
    """granite-4.0-h-small as published (``config.json``)."""
    vocab_size: int = 100352
    d_model: int = 4096
    layer_types: tuple = _PERIOD * 4
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 128
    # the mixer (the names ``falcon_h1``'s shared code reads)
    d_ssm: int = 8192                 # mamba_n_heads x mamba_d_head
    ssm_heads: int = 128
    ssm_head_dim: int = 64
    ssm_state: int = 128
    ssm_groups: int = 1
    ssm_conv: int = 4
    ssm_chunk: int = 128              # the program's own (published: 256)
    # the experts
    d_expert: int = 768               # one routed expert's width
    d_shared: int = 1536              # the shared expert's
    n_experts: int = 72               # the router's width
    n_experts_held: int = 72          # experts whose weights are here,
    first_expert: int = 0             # from this one
    top_k: int = 10
    # the four multipliers
    embedding_multiplier: float = 12.0
    attention_multiplier: float = 0.0078125
    residual_multiplier: float = 0.22
    logits_scaling: float = 16.0
    rms_eps: float = 1e-5
    dtype: str = "bfloat16"
    tie_embeddings: bool = True

    def __post_init__(self):
        if set(self.layer_types) - {"mamba", "attention"}:
            raise ValueError(
                "a layer's mixer is mamba or attention, not "
                f"{sorted(set(self.layer_types) - {'mamba', 'attention'})}")
        if self.ssm_heads * self.ssm_head_dim != self.d_ssm:
            raise ValueError(
                f"{self.ssm_heads} mixer heads of {self.ssm_head_dim} are "
                f"not {self.d_ssm}")
        if self.ssm_heads % self.ssm_groups:
            raise ValueError(f"{self.ssm_groups} groups do not divide "
                             f"{self.ssm_heads} heads")
        if not 0 <= self.first_expert <= self.n_experts - self.n_experts_held:
            raise ValueError(
                f"experts {self.first_expert} to "
                f"{self.first_expert + self.n_experts_held} are not among "
                f"the router's {self.n_experts}")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def conv_dim(self) -> int:
        """Width of ``xBC``: x, then B and C over the groups."""
        return self.d_ssm + 2 * self.ssm_groups * self.ssm_state

    @property
    def param_dtype(self):
        return jnp.dtype(self.dtype)


def granite_4_0_h_small() -> GraniteMoeHybridConfig:
    """As published: 40 layers, every expert held."""
    return GraniteMoeHybridConfig()


def granite_moe_hybrid_tiny(vocab_size: int = 128,
                            **changes) -> GraniteMoeHybridConfig:
    """Test-size config in float32: two mixers, attention, a mixer; query
    groups of 4, one mixer group, a scan chunk of 8 so that a short
    prompt spans several chunks, 8 of 16 experts held, 4 a token, every
    multiplier another number than one and the attention's not ``head_dim
    ** -0.5``."""
    kw = dict(vocab_size=vocab_size, d_model=64,
              layer_types=("mamba", "mamba", "attention", "mamba"),
              n_heads=8, n_kv_heads=2, head_dim=8, d_ssm=64, ssm_heads=8,
              ssm_head_dim=8, ssm_state=16, ssm_groups=1, ssm_chunk=8,
              d_expert=24, d_shared=48, n_experts=16, n_experts_held=8,
              top_k=4, embedding_multiplier=3.0, attention_multiplier=0.125,
              residual_multiplier=0.4, logits_scaling=2.0, dtype="float32")
    kw.update(changes)
    return GraniteMoeHybridConfig(**kw)


# ---------------------------------------------------------------------------
# The layer plan
# ---------------------------------------------------------------------------

def _runs(cfg: GraniteMoeHybridConfig) -> list:
    """Runs of consecutive layers of one type: (key, type, first layer,
    layers)."""
    runs = []
    for i, kind in enumerate(cfg.layer_types):
        if runs and runs[-1][1] == kind:
            runs[-1][2] += 1
        else:
            runs.append([i, kind, 1])
    return [(f"layers{first}" + (f"-{first + n - 1}" if n > 1 else ""),
             kind, first, n) for first, kind, n in runs]


def recurrent_state(cfg: GraniteMoeHybridConfig) -> falcon_h1.RecurrentState:
    """What a sequence keeps in one ``mamba`` layer."""
    return falcon_h1.RecurrentState(
        (("ssm_state", (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
          "float32"),
         ("conv_tail", (cfg.ssm_conv - 1, cfg.conv_dim), cfg.dtype)),
        cfg.ssm_chunk)


def layer_plan(cfg: GraniteMoeHybridConfig) -> tuple:
    """The runs in order, each with what its layers hold and do: a mixer
    and its state, or attention and its K/V pages, and in either case the
    experts behind it."""
    state = recurrent_state(cfg)
    return tuple(
        LayerStack(key, "full", None, n,
                   state=state if kind == "mamba" else None,
                   attends=kind == "attention", feeds=True)
        for key, kind, _, n in _runs(cfg))


def rotary_tables(cfg: GraniteMoeHybridConfig, positions) -> dict:
    """No rotary embedding: attention's one kind takes no table."""
    return {"full": ()}


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

# Seeded weights that the four published multipliers and the TIED head
# leave a working network, that a bf16 program and a float32 reference can
# agree on, and on which each mechanism still shows in the logits (the
# argument is ``models/falcon_h1.py:init_params``'s for the multipliers
# and ``models/nemotron_h.py``'s for the routed part; the tied head's is
# new here):
# - the multipliers stand against trained weights that are large where
#   the multiplier is small. Random weights at the plain fan-in scale do
#   not: logits over ``logits_scaling`` would lie within a sixteenth of
#   each other (ANY token would pass the reference check), scores times
#   ``attention_multiplier`` would be uniform attention, and branches
#   times ``residual_multiplier`` would vanish beside an embedding times
#   twelve. So each matrix is drawn at the fan-in scale OVER the
#   multiplier that follows it: the embedding's rows at ``_LOGIT_STD *
#   logits_scaling / sqrt(d)`` (the tied head's logits then have that
#   deviation over the vocabulary; the stream starts at
#   ``embedding_multiplier`` times the rows' scale),
#   q and k each over the square root of
#   ``attention_multiplier * sqrt(head_dim)`` (scores of unit variance
#   under the PUBLISHED scale; under ``head_dim ** -0.5`` they would be
#   eleven times that, one key a query), and every output projection over
#   ``residual_multiplier``;
# - a head tied to a RANDOM embedding answers every token with itself:
#   the stream holds the token's own row, a share ``f`` of its norm, and
#   that row against itself is ``sqrt(d)`` times what it is against
#   another (64 at the published width), so with the branches the
#   embedding's size the greedy token would be the input's by a margin of
#   tens of logits and no departure could move it (a trained model learns
#   its way out of this; seeded weights must be drawn out of it): the
#   branches have to outgrow the embedding ``sqrt(d)`` times over. Grown
#   evenly over the layers (each branch 0.8 of the stream that enters it)
#   they make a chaotic network: a bf16 piece's 0.4% error was 11% of the
#   logits' deviation ten layers on, the greedy token 0.16-0.25 short of
#   the reference's best on three seeds of three, with every departure at
#   2-6 (a v5e at the cell's widths, my chip runs, PR 62: a relative
#   error grows by ``sqrt((1 + c^2 g^2) / (1 + c^2))`` a branch of size
#   ``c`` of its stream and gain ``g``, 1.13 at c = 0.8 and 1.03 at a
#   third). So the growth is taken in TWO steps where it costs one gain
#   each: each of the first two layers' mixers is ``d ** 0.25`` times the
#   stream that enters it, and every other branch a third of the stream
#   that enters it (``_branch_sizes``). Between the two steps the
#   embedding is still a ninth of the stream, where
#   ``embedding_multiplier`` is felt; at the head its own row weighs less
#   than one random row does;
# - attention over hundreds of random keys averages its values to a tenth
#   of another branch's size (Laguna's finding), and here it is one layer
#   in ten with no rotary embedding to tell two keys apart: ``wo`` is at
#   ``_ATTENTION_OUT_GAIN`` times the branches' scale (at 4 a reference
#   that rotates q and k read 0.176 on one seed of two, at 8 0.178-0.378
#   on eight of eight);
# - a bf16 stream's rounding tips which expert a token takes in a few
#   percent of the token-layers whatever the router's scale
#   (``models/nemotron_h.py``). Ten of 72 tips more often than six of
#   128, but the tipped expert is the LEAST of the ten and its softmax
#   weight the smallest (about 0.06 of the ten's, where Nemotron-H's
#   sigmoids stand near a sixth each), so the routed experts' ``wo_e``
#   stands at ``_ROUTED_OUT_GAIN`` of the branches' scale, and that gain
#   is a balance with little room: what tips and what a reference that
#   does not renormalise its ten weights (``gating="softmax_all"``)
#   misses by both grow with it. At 1 the check read 0.000-0.033 on
#   fourteen seeds and that departure 0.207 and 0.096, under the limit;
#   at 2 the departure 0.290-0.500 and the check 0.003-0.079 on eleven
#   seeds, too near it; at 1.25 the check 0.002-0.039 on thirteen seeds
#   and the departure 0.134-0.311 on eight of eight;
# - the logits are drawn to a deviation of ``_LOGIT_STD`` over the
#   vocabulary, and not to one as the other families' are: a stream that
#   IS its branches carries their bf16 error undiluted (the others'
#   streams are half embedding, which is exact), 2.7% of the logits'
#   deviation at best here, and at a deviation of one the check read
#   0.030 / 0.000 / 0.091 on three seeds. Every gap the check can read,
#   the error's and the departures' alike, scales with it;
# - the mixer's own parameters are Mamba-2's initialisation (``A_log =
#   log(uniform(1, 16))``, ``dt_bias`` the inverse softplus of a
#   log-uniform step in [0.001, 0.1], ``D`` one, the filter at the fan-in
#   scale of its taps and its bias at 0.3); norm vectors one; the router
#   at the fan-in scale (logits of unit variance: the ten chosen of 72
#   hold about two fifths of a softmax over all).
# All numbers: a v5e at the cell's widths, my chip runs, PR 62
# (``scripts/check_seeds.py``: 64 served tokens a seed against the
# harness's limit of 0.1; ``PERF.md`` Findings).
_DT_MIN, _DT_MAX = 0.001, 0.1
_ATTENTION_OUT_GAIN = 8.0
_ROUTED_OUT_GAIN = 1.25
_LOGIT_STD = 0.5


def _embedding_std(cfg: GraniteMoeHybridConfig) -> float:
    """What the embedding's entries are drawn at: the tied head's logits
    then have a deviation of ``_LOGIT_STD`` over the vocabulary."""
    return _LOGIT_STD * cfg.logits_scaling * cfg.d_model ** -0.5


def _branch_sizes(cfg: GraniteMoeHybridConfig):
    """The rms each layer's two branches are drawn to, [layers, 2] (the
    mixer's, the experts'), in units of the stream's start: each of the
    first two mixers ``d ** 0.25`` times the stream that enters it, every
    other branch a third of the stream that enters it (the note above)."""
    lead, stream, sizes = cfg.d_model ** 0.25, 1.0, []
    for layer in range(cfg.n_layers):
        mixer = stream * (lead if layer < 2 else 1.0 / 3.0)
        stream = math.hypot(stream, mixer)
        experts = stream / 3.0
        stream = math.hypot(stream, experts)
        sizes.append((mixer, experts))
    return jnp.array(sizes, jnp.float32)


def _init_run(cfg: GraniteMoeHybridConfig, kind: str, first: int, n: int,
              key) -> dict:
    dt = cfg.param_dtype
    d, di, c = cfg.d_model, cfg.d_ssm, cfg.conv_dim
    e, f, fs = cfg.n_experts_held, cfg.d_expert, cfg.d_shared
    # what the output projections are drawn at, of the fan-in scale: the
    # branch's size over the multiplier that follows
    mixer_out, experts_out = (
        _branch_sizes(cfg)[first:first + n].T
        * (cfg.embedding_multiplier * _embedding_std(cfg)
           / cfg.residual_multiplier))

    def dense(key, shape, fan_in, dtype=dt, gain=1.0):
        return (fanin_init(key, shape, fan_in) * gain).astype(dtype)

    ks = jax.random.split(key, 13)
    p = {"norm": jnp.ones((n, d), dtype=dt),
         "ffn_norm": jnp.ones((n, d), dtype=dt),
         "router": dense(ks[0], (n, d, cfg.n_experts), d, dtype=jnp.float32),
         "wi_gate": dense(ks[1], (n, e, d, f), d),
         "wi_up": dense(ks[2], (n, e, d, f), d),
         "wo_e": dense(ks[3], (n, e, f, d), f,
                       gain=experts_out[:, None, None, None]
                       * _ROUTED_OUT_GAIN),
         "ws_gate": dense(ks[4], (n, d, fs), d),
         "ws_up": dense(ks[5], (n, d, fs), d),
         "ws_down": dense(ks[6], (n, fs, d), fs,
                          gain=experts_out[:, None, None])}
    if kind == "mamba":
        step = jnp.exp(jax.random.uniform(
            ks[11], (n, cfg.ssm_heads), jnp.float32,
            math.log(_DT_MIN), math.log(_DT_MAX)))
        p.update(
            in_proj=dense(ks[7], (n, d, di + c + cfg.ssm_heads), d),
            conv_w=dense(ks[8], (n, c, cfg.ssm_conv), cfg.ssm_conv),
            conv_b=(0.3 * jax.random.normal(ks[9], (n, c), jnp.float32)
                    ).astype(dt),
            dt_bias=step + jnp.log(-jnp.expm1(-step)),  # softplus^-1(step)
            A_log=jnp.log(jax.random.uniform(
                ks[12], (n, cfg.ssm_heads), jnp.float32, 1.0, 16.0)),
            D=jnp.ones((n, cfg.ssm_heads), jnp.float32),
            ssm_norm=jnp.ones((n, di), dtype=dt),
            out_proj=dense(ks[10], (n, di, d), di,
                           gain=mixer_out[:, None, None]))
    else:
        qdim, kvdim = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
        qk = (cfg.attention_multiplier * cfg.head_dim ** 0.5) ** -0.5
        gain = jnp.concatenate([jnp.full((qdim + kvdim,), qk),
                                jnp.ones((kvdim,))])
        p.update(
            wqkv=dense(ks[7], (n, d, qdim + 2 * kvdim), d, gain=gain),
            wo=dense(ks[8], (n, qdim, d), qdim,
                     gain=mixer_out[:, None, None] * _ATTENTION_OUT_GAIN))
    return p


def init_params(cfg: GraniteMoeHybridConfig, key) -> dict:
    """The parameter pytree: ``blocks`` maps each run's key to its
    stacked weights (the router, ``dt_bias``, ``A_log`` and ``D`` in
    float32). Scales: the note above."""
    dt = cfg.param_dtype
    runs = _runs(cfg)
    k_emb, k_head, *k_runs = jax.random.split(key, 2 + len(runs))
    d = cfg.d_model
    params = {
        "embedding": (fanin_init(k_emb, (cfg.vocab_size, d), 1)
                      * _embedding_std(cfg)).astype(dt),
        "blocks": {name: _init_run(cfg, kind, first, n, k)
                   for (name, kind, first, n), k in zip(runs, k_runs)},
        "final_norm": jnp.ones((d,), dtype=dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = (fanin_init(k_head, (d, cfg.vocab_size), 1)
                             * _embedding_std(cfg)).astype(dt)
    return params


# ---------------------------------------------------------------------------
# The layers' pieces
# ---------------------------------------------------------------------------

def _residual(cfg, x, term):
    """``x + residual_multiplier * term``, ``term`` float32: the
    multiplier on the float32 sum, before its one rounding."""
    return x + (term * cfg.residual_multiplier).astype(x.dtype)


def embed(cfg: GraniteMoeHybridConfig, params, tokens):
    """Token ids -> the stream's start, ``embedding_multiplier`` applied."""
    with jax.named_scope(scopes.EMBED):
        x = params["embedding"][tokens]
        return (x.astype(jnp.float32)
                * cfg.embedding_multiplier).astype(x.dtype)


def head_logits(cfg: GraniteMoeHybridConfig, params, x):
    """Normed last hidden states [b, d] -> float32 logits [b, vocab] over
    ``logits_scaling``. A tied head contracts over the embedding's own
    layout [vocab, d]: the same numbers as ``x @ embedding.T`` with no
    transpose for a compiler to build (0.41 GB in and out a decode step
    at the published widths, were it built)."""
    with jax.named_scope(scopes.LM_HEAD):
        if cfg.tie_embeddings:
            logits = jnp.einsum("bd,vd->bv", x, params["embedding"],
                                preferred_element_type=jnp.float32)
        else:
            logits = jnp.einsum("bd,dv->bv", x, params["lm_head"],
                                preferred_element_type=jnp.float32)
        return logits / cfg.logits_scaling


def _mixer_out(cfg, p, y, xs, z):
    """The mixer's end: the gated norm over the whole inner width (one
    group), ``out_proj``, ``residual_multiplier``: the term the block
    adds to its stream, in the model's dtype."""
    y = falcon_h1.gated_norm(cfg, p, y, xs, z)
    out = jnp.einsum("...k,kd->...d", y, p["out_proj"],
                     preferred_element_type=jnp.float32)
    return (out * cfg.residual_multiplier).astype(p["out_proj"].dtype)


_ENDS = (falcon_h1.plain_mixer_in, _mixer_out)


def recurrent_mixer(cfg: GraniteMoeHybridConfig, p, x, state, valid):
    """A ``mamba`` layer's mixer over a padded block, from each row's
    ``state``: (the term to add to the stream, the state after each row's
    last valid token). ``falcon_h1.recurrent_mixer`` between this
    module's ends."""
    return falcon_h1.recurrent_mixer(cfg, p, x, state, valid, ends=_ENDS)


def recurrent_step(cfg: GraniteMoeHybridConfig, p, x, state, layer, active):
    """A ``mamba`` layer's mixer for one token a slot over the slots'
    STACKED state arrays, this layer's at [layer] (its place among the
    layers that keep state): ``falcon_h1.recurrent_step`` between this
    module's ends."""
    return falcon_h1.recurrent_step(cfg, p, x, state, layer, active,
                                    ends=_ENDS)


def attention_projections(cfg: GraniteMoeHybridConfig, p, x):
    """What an ``attention`` layer's attention takes in, from the stream
    ``x`` [b, s, d]: the layer's norm, q | k | v from the one stack, in
    heads, no rotary embedding. The scores' published scale is
    ``attention_multiplier``; whoever attends scales by
    ``page_attention_scale(head_dim)``, so q carries the ratio of the
    two, multiplied onto the float32 products before their one rounding.
    Returns (q [b, s, heads, hd], k, v [b, s, kv heads, hd])."""
    b, s, _ = x.shape
    qdim, kvdim = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    fold = cfg.attention_multiplier / page_attention_scale(cfg.head_dim)
    with jax.named_scope(scopes.ATTN_QKV):
        u = rms_norm(x, p["norm"], eps=cfg.rms_eps)
        q, k, v = jnp.split(
            jnp.einsum("bsd,dk->bsk", u, p["wqkv"],
                       preferred_element_type=jnp.float32),
            [qdim, qdim + kvdim], axis=-1)
        return tuple(y.astype(x.dtype).reshape(b, s, -1, cfg.head_dim)
                     for y in (q * fold, k, v))


def attention_output(cfg: GraniteMoeHybridConfig, p, x, attn):
    """An ``attention`` layer's mixer's end: the heads' outputs through
    ``wo``, times ``residual_multiplier``, added to ``x`` [b, s, d]."""
    b, s, _ = x.shape
    with jax.named_scope(scopes.ATTN_OUT):
        return _residual(cfg, x, jnp.einsum(
            "bsq,qd->bsd", attn.reshape(b, s, -1), p["wo"],
            preferred_element_type=jnp.float32))


def feed_forward(cfg: GraniteMoeHybridConfig, p, x, valid=None,
                 stacked=None):
    """Every layer's second sublayer over ``x`` [b, s, d]: the held
    routed experts' part for the tokens routed to them plus the shared
    expert on every token, SwiGLU both, times ``residual_multiplier``;
    returns (the residual-added stream, statistics over the HELD experts,
    as ``models/laguna.py:feed_forward``'s). ``valid`` [b, s] marks the
    rows that are tokens. ``stacked``: (the run's weights stacked on
    their layer axis, this layer's index in them), from a program that
    scans the run: the expert stacks are then read from there in place
    (``moe_ffn_dropless``'s ``layer``), not from ``p``'s slices."""
    b, s, d = x.shape
    h = rms_norm(x, p["ffn_norm"], eps=cfg.rms_eps)
    held, layer = (p, None) if stacked is None else stacked
    routed, load = moe_ffn_dropless(
        h.reshape(b * s, d), p["router"], held["wi_gate"], held["wi_up"],
        held["wo_e"], layer=layer, top_k=cfg.top_k, norm_topk_prob=True,
        first_expert=cfg.first_expert,
        valid=None if valid is None else valid.reshape(b * s),
        scoring="softmax", form="swiglu")
    with jax.named_scope(scopes.SHARED_EXPERT):
        gated = jax.nn.silu(h @ p["ws_gate"]) * (h @ p["ws_up"])
        shared = jnp.einsum("bsf,fd->bsd", gated, p["ws_down"],
                            preferred_element_type=jnp.float32)
    stats = share_statistics(load, valid, b * s, cfg.top_k)
    with jax.named_scope(scopes.MOE_COMBINE):
        return _residual(cfg, x, routed.reshape(b, s, d) + shared), stats


def zero_state(cfg: GraniteMoeHybridConfig, rows: int) -> tuple:
    """The state of ``rows`` sequences in one ``mamba`` layer before
    their first token."""
    return tuple(jnp.zeros((rows, *shape), dtype)
                 for _, shape, dtype in recurrent_state(cfg).arrays)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def forward(cfg: GraniteMoeHybridConfig, params: dict, tokens):
    """Token ids [batch, seq] -> logits [batch, seq, vocab] (fp32): the
    plain causal path, the runs of the layer plan one after another,
    every sequence from a zero state. ``seq`` is padded to whole scan
    chunks inside (and cut again)."""
    b, s = tokens.shape
    q = min(cfg.ssm_chunk, s)
    pad = (-s) % q
    tokens = jnp.pad(tokens, ((0, 0), (0, pad)))
    valid = jnp.broadcast_to(jnp.arange(s + pad) < s, (b, s + pad))
    x = embed(cfg, params, tokens)
    start = jnp.zeros((b,), jnp.int32)
    state = zero_state(cfg, b)
    for run in layer_plan(cfg):

        def block(x, p, run=run):
            if run.attends:
                q_, k, v = attention_projections(cfg, p, x)
                attn = cached_attention(
                    q_, k, v, start, scale=page_attention_scale(cfg.head_dim))
                x = attention_output(cfg, p, x, attn)
            else:
                x = x + recurrent_mixer(cfg, p, x, state, valid)[0]
            x, _ = feed_forward(cfg, p, x, valid=valid)
            return x, None

        x, _ = lax.scan(block, x, params["blocks"][run.key])
    x = rms_norm(x, params["final_norm"], eps=cfg.rms_eps)[:, :s]
    return head_logits(cfg, params, x.reshape(b * s, -1)).reshape(b, s, -1)
