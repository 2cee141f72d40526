"""Nemotron-H-family hybrid decoder (NVIDIA Nemotron 3 Nano,
``model_type`` ``nemotron_h``), for serving.

A stack whose every layer is ONE thing under one pre-norm and one
residual, ``h <- h + Mix_i(rms(h, norm_i))``, the letter of
``hybrid_override_pattern`` saying which:

- ``M``, a Mamba-2 mixer: Falcon-H1's mixer without its multipliers
  (``models/falcon_h1.py`` has the equations and, between this module's
  two ends of it, the code: ``recurrent_mixer``, ``recurrent_step``,
  ``ops/ssm.py``). A sequence keeps the float32 state ``S`` [H, P, N] and
  the convolution's tail in such a layer, and no page;
- ``*``, attention: grouped-query, causal, scale ``head_dim ** -0.5``
  (the engine's own: ``ops/paged_attention.py:page_attention_scale``),
  q | k | v from one stack ``wqkv``, and NO rotary embedding (the mixers
  carry position). A sequence keeps K/V pages in such a layer and
  nothing else;
- ``E``, routed experts: sigmoid scores over ``n_experts``, the ``top_k``
  of largest score + correction bias, the chosen scores over their sum,
  times ``routed_scale``; each expert ``relu(u W_up) ** 2 W_down``, two
  matrices and no gate, plus one shared expert of the same form on every
  token. Of the routed experts this process may hold a share
  (``n_experts_held`` from ``first_expert``), as Laguna's does
  (``ops.moe.moe_ffn_dropless``). A sequence keeps nothing in such a
  layer.

``layer_plan`` says so, run by run (``LayerStack.attends``, ``state``,
``feeds``), and the serving engine's stores have the layers that keep
them: K/V pools over the ``*`` layers, state arrays over the ``M``
layers. ``params["blocks"]`` maps a run's key to its weights stacked on a
leading axis; consecutive layers of one letter are one run (the
published pattern has none).

The pieces carry ``jax.named_scope``s (``ops/scopes.py``: ``attn_qkv``,
``attn_out``, ``shared_expert``; the mixer's are ``models/falcon_h1.py``'s,
the routed feed-forward's ``ops/moe.py``'s): the mixer's ``out_proj`` and
attention's ``wo`` are both [4096, 2688] at the published widths, and a
lowered program names what shapes cannot. No training path: there are no
logical axes and no loss here.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models import falcon_h1
from ray_tpu.models.llama import (  # noqa: F401 - embed, head_logits:
    LayerStack, embed, fanin_init,  # pieces of the block's module that
    head_logits, lm_head_weights)   # are Llama's
from ray_tpu.ops import scopes
from ray_tpu.ops.attention import cached_attention
from ray_tpu.ops.moe import moe_ffn_dropless, share_statistics
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.paged_attention import page_attention_scale

_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


@dataclass(frozen=True)
class NemotronHConfig:
    """NVIDIA-Nemotron-3-Nano-30B-A3B as published (``config.json``)."""
    vocab_size: int = 131072
    d_model: int = 2688
    pattern: str = _PATTERN           # hybrid_override_pattern
    n_heads: int = 32
    n_kv_heads: int = 2
    head_dim: int = 128
    # the mixer (the names ``falcon_h1``'s shared code reads)
    d_ssm: int = 4096                 # mamba_num_heads x mamba_head_dim
    ssm_heads: int = 64
    ssm_head_dim: int = 64
    ssm_state: int = 128
    ssm_groups: int = 8
    ssm_conv: int = 4
    ssm_chunk: int = 128
    time_step_min: float = 0.001      # the seeded ``dt_bias`` alone
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # the experts
    d_expert: int = 1856              # one routed expert's width
    d_shared: int = 3712              # the shared expert's
    n_experts: int = 128              # the router's width
    n_experts_held: int = 128         # experts whose weights are here,
    first_expert: int = 0             # from this one
    top_k: int = 6
    norm_topk_prob: bool = True
    routed_scale: float = 2.5
    rms_eps: float = 1e-5
    dtype: str = "bfloat16"
    tie_embeddings: bool = False

    def __post_init__(self):
        if set(self.pattern) - set("ME*"):
            raise ValueError(f"a layer is M, E or *, not "
                             f"{sorted(set(self.pattern) - set('ME*'))}")
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
        return len(self.pattern)

    @property
    def conv_dim(self) -> int:
        """Width of ``xBC``: x, then B and C over the groups."""
        return self.d_ssm + 2 * self.ssm_groups * self.ssm_state

    @property
    def param_dtype(self):
        return jnp.dtype(self.dtype)


def nemotron_3_nano_30b_a3b() -> NemotronHConfig:
    """As published: 52 layers, every expert held."""
    return NemotronHConfig()


def nemotron_h_tiny(vocab_size: int = 128, **changes) -> NemotronHConfig:
    """Test-size config in float32: the published pattern's first nine
    letters, query groups of 4, four mixer groups of two heads, a scan
    chunk of 8 so that a short prompt spans several chunks, 4 of 8
    experts held."""
    kw = dict(vocab_size=vocab_size, d_model=64, pattern=_PATTERN[:9],
              n_heads=8, n_kv_heads=2, head_dim=16, d_ssm=64, ssm_heads=8,
              ssm_head_dim=8, ssm_state=16, ssm_groups=4, ssm_chunk=8,
              d_expert=24, d_shared=48, n_experts=8, n_experts_held=4,
              top_k=3, dtype="float32")
    kw.update(changes)
    return NemotronHConfig(**kw)


# ---------------------------------------------------------------------------
# The layer plan
# ---------------------------------------------------------------------------

def _runs(cfg: NemotronHConfig) -> list:
    """Runs of consecutive layers of one letter: (key, letter, layers)."""
    runs = []
    for i, letter in enumerate(cfg.pattern):
        if runs and runs[-1][1] == letter:
            runs[-1][2] += 1
        else:
            runs.append([i, letter, 1])
    return [(f"layers{first}" + (f"-{first + n - 1}" if n > 1 else ""),
             letter, n) for first, letter, n in runs]


def recurrent_state(cfg: NemotronHConfig) -> falcon_h1.RecurrentState:
    """What a sequence keeps in one ``M`` layer."""
    return falcon_h1.RecurrentState(
        (("ssm_state", (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
          "float32"),
         ("conv_tail", (cfg.ssm_conv - 1, cfg.conv_dim), cfg.dtype)),
        cfg.ssm_chunk)


def layer_plan(cfg: NemotronHConfig) -> tuple:
    """The runs in order, each with what its layers hold and do: a mixer
    and its state alone, attention and its K/V pages alone, or a
    feed-forward alone."""
    state = recurrent_state(cfg)
    return tuple(
        LayerStack(key, "full", None, n,
                   state=state if letter == "M" else None,
                   attends=letter == "*", feeds=letter == "E")
        for key, letter, n in _runs(cfg))


def rotary_tables(cfg: NemotronHConfig, positions) -> dict:
    """No rotary embedding: attention's one kind takes no table."""
    return {"full": ()}


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

# Seeded weights that a bf16 program and a float32 reference can agree on
# and on which each mechanism still shows in the logits (the argument is
# ``models/laguna.py:init_params``'s and ``models/falcon_h1.py``'s; the
# numbers are a v5e's at the cell's widths, my chip runs, PR 43: the bf16
# ``forward`` against the reference over every position of 480-token
# prompts, ``PERF.md`` Findings):
# - the embedding's rows have unit variance; every layer is one residual
#   branch, and the output projections (``out_proj``, ``ws_down``) are at
#   1/sqrt(layers) of the fan-in scale (GPT-2's residual scaling over the
#   stack's branches), so that the stream stays the embedding plus
#   branches a third its size;
# - attention over hundreds of random keys averages its values to a tenth
#   of another branch's size (Laguna's finding), and here it is one layer
#   in nine with no rotary embedding to tell two keys apart: ``wo`` is at
#   ``_ATTENTION_OUT_GAIN`` times the fan-in scale;
# - a bf16 stream's rounding tips which expert a token takes in 4-5% of
#   the token-layers WHATEVER the router's scale and the bias's (gains of
#   1 to 16, bias deviations of 0.01 to 3: a row in six to a row in
#   twenty read a spiked error; only a bias that fixes the choice for
#   every token, deviation 10, ends it), and the sigmoids of the chosen
#   six lie near one, so a tipped choice swaps a sixth of the routed
#   part. With the routed experts' ``wo_e`` at the branches' scale a
#   tipped row's logits read 0.1-0.27 rms off and the greedy token fell
#   up to 0.60 short of the reference's best (15 positions of 480 past
#   the harness's 0.1); so ``wo_e`` stands at ``_ROUTED_OUT_GAIN`` of it:
#   at 0.3 one position of 3,360 read past 0.1, at 0.25 none of 1,440
#   (0.089 at most), at 0.2 the cell's checks read 0.000-0.037. The
#   departures that touch the routed part alone shrink with it
#   (``scale=1`` 0.12-0.14 at 0.2, where the others read 0.2-0.9);
# - the router is at ``_ROUTER_GAIN`` times the fan-in scale and the
#   correction bias N(0, ``_ROUTER_BIAS_STD`` ** 2), as
#   ``models/dots3_note.py``'s: 128 tokens reach 17-23 of the 64 held
#   experts, the busiest ten times the mean;
# - the mixer's own parameters are Mamba-2's initialisation: ``A_log =
#   log(uniform(1, 16))``, ``dt_bias`` the inverse softplus of a
#   log-uniform step in [``time_step_min``, ``time_step_max``] floored at
#   ``time_step_floor``, ``D`` one, the filter at the fan-in scale of its
#   taps and its bias at 0.3; norm vectors one.
_ATTENTION_OUT_GAIN = 4.0
_ROUTED_OUT_GAIN = 0.2          # of the branches' 1/sqrt(layers)
_ROUTER_GAIN = 4.0
_ROUTER_BIAS_STD = 0.1


def _init_run(cfg: NemotronHConfig, letter: str, n: int, key) -> dict:
    dt = cfg.param_dtype
    d, di, c = cfg.d_model, cfg.d_ssm, cfg.conv_dim
    branch = cfg.n_layers ** -0.5

    def dense(key, shape, fan_in, dtype=dt, gain=1.0):
        return (fanin_init(key, shape, fan_in) * gain).astype(dtype)

    ks = jax.random.split(key, 6)
    p = {"norm": jnp.ones((n, d), dtype=dt)}
    if letter == "M":
        step = jnp.maximum(jnp.exp(jax.random.uniform(
            ks[4], (n, cfg.ssm_heads), jnp.float32,
            math.log(cfg.time_step_min), math.log(cfg.time_step_max))),
            cfg.time_step_floor)
        p.update(
            in_proj=dense(ks[0], (n, d, di + c + cfg.ssm_heads), d),
            conv_w=dense(ks[1], (n, c, cfg.ssm_conv), cfg.ssm_conv),
            conv_b=(0.3 * jax.random.normal(ks[2], (n, c), jnp.float32)
                    ).astype(dt),
            dt_bias=step + jnp.log(-jnp.expm1(-step)),  # softplus^-1(step)
            A_log=jnp.log(jax.random.uniform(
                ks[5], (n, cfg.ssm_heads), jnp.float32, 1.0, 16.0)),
            D=jnp.ones((n, cfg.ssm_heads), jnp.float32),
            ssm_norm=jnp.ones((n, di), dtype=dt),
            out_proj=dense(ks[3], (n, di, d), di, gain=branch))
    elif letter == "*":
        qdim, kvdim = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
        p.update(
            wqkv=dense(ks[0], (n, d, qdim + 2 * kvdim), d),
            wo=dense(ks[1], (n, qdim, d), qdim, gain=_ATTENTION_OUT_GAIN))
    else:
        e, f, fs = cfg.n_experts_held, cfg.d_expert, cfg.d_shared
        p.update(
            router=dense(ks[0], (n, d, cfg.n_experts), d,
                         dtype=jnp.float32, gain=_ROUTER_GAIN),
            router_bias=_ROUTER_BIAS_STD * jax.random.normal(
                ks[1], (n, cfg.n_experts), jnp.float32),
            wi_up=dense(ks[2], (n, e, d, f), d),
            wo_e=dense(ks[3], (n, e, f, d), f,
                       gain=branch * _ROUTED_OUT_GAIN),
            ws_up=dense(ks[4], (n, d, fs), d),
            ws_down=dense(ks[5], (n, fs, d), fs, gain=branch))
    return p


def init_params(cfg: NemotronHConfig, key) -> dict:
    """The parameter pytree: ``blocks`` maps each run's key to its
    stacked weights (router and correction bias, ``dt_bias``, ``A_log``
    and ``D`` in float32). Scales: the note above."""
    dt = cfg.param_dtype
    runs = _runs(cfg)
    k_emb, k_head, *k_runs = jax.random.split(key, 2 + len(runs))
    d = cfg.d_model
    params = {
        "embedding": fanin_init(k_emb, (cfg.vocab_size, d), 1).astype(dt),
        "blocks": {name: _init_run(cfg, letter, n, k)
                   for (name, letter, n), k in zip(runs, k_runs)},
        "final_norm": jnp.ones((d,), dtype=dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = fanin_init(
            k_head, (d, cfg.vocab_size), d).astype(dt)
    return params


# ---------------------------------------------------------------------------
# The layers' pieces
# ---------------------------------------------------------------------------

def _mixer_out(cfg, p, y, xs, z):
    """The mixer's end: the gated grouped norm, then ``out_proj``."""
    y = falcon_h1.gated_norm(cfg, p, y, xs, z)
    return jnp.einsum("...k,kd->...d", y, p["out_proj"],
                      preferred_element_type=jnp.float32
                      ).astype(p["out_proj"].dtype)


_ENDS = (falcon_h1.plain_mixer_in, _mixer_out)


def recurrent_mixer(cfg: NemotronHConfig, p, x, state, valid):
    """An ``M`` layer over a padded block, from each row's ``state``:
    (the term to add to the stream, the state after each row's last
    valid token). ``falcon_h1.recurrent_mixer`` between this module's
    ends."""
    return falcon_h1.recurrent_mixer(cfg, p, x, state, valid, ends=_ENDS)


def recurrent_step(cfg: NemotronHConfig, p, x, state, layer, active):
    """An ``M`` layer for one token a slot over the slots' STACKED state
    arrays, this layer's at [layer] (its place among the layers that keep
    state): ``falcon_h1.recurrent_step`` between this module's ends."""
    return falcon_h1.recurrent_step(cfg, p, x, state, layer, active,
                                    ends=_ENDS)


def attention_projections(cfg: NemotronHConfig, p, x):
    """What a ``*`` layer's attention takes in, from the stream ``x``
    [b, s, d]: the layer's norm, q | k | v from the one stack, in heads.
    No rotary embedding. Returns (q [b, s, heads, hd], k, v [b, s, kv
    heads, hd])."""
    b, s, _ = x.shape
    qdim, kvdim = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    with jax.named_scope(scopes.ATTN_QKV):
        u = rms_norm(x, p["norm"], eps=cfg.rms_eps)
        return tuple(y.reshape(b, s, -1, cfg.head_dim) for y in jnp.split(
            u @ p["wqkv"], [qdim, qdim + kvdim], axis=-1))


def attention_output(cfg: NemotronHConfig, p, x, attn):
    """A ``*`` layer's end: the heads' outputs through ``wo``, added to
    ``x`` [b, s, d]."""
    b, s, _ = x.shape
    with jax.named_scope(scopes.ATTN_OUT):
        return x + attn.reshape(b, s, -1) @ p["wo"]


def feed_forward(cfg: NemotronHConfig, p, x, valid=None, stacked=None):
    """An ``E`` layer over ``x`` [b, s, d]: the held routed experts' part
    for the tokens routed to them plus the shared expert on every token,
    squared-ReLU experts of two matrices; returns (the residual-added
    stream, statistics over the HELD experts, as
    ``models/laguna.py:feed_forward``'s). ``valid`` [b, s] marks the rows
    that are tokens. ``stacked``: (the run's weights stacked on their layer
    axis, this layer's index in them), from a program that scans the run:
    the expert stacks are then read from there in place
    (``moe_ffn_dropless``'s ``layer``), not from ``p``'s slices."""
    b, s, d = x.shape
    h = rms_norm(x, p["norm"], eps=cfg.rms_eps)
    held, layer = (p, None) if stacked is None else stacked
    routed, load = moe_ffn_dropless(
        h.reshape(b * s, d), p["router"], None, held["wi_up"], held["wo_e"],
        layer=layer,
        top_k=cfg.top_k, norm_topk_prob=cfg.norm_topk_prob,
        routed_scale=cfg.routed_scale, first_expert=cfg.first_expert,
        valid=None if valid is None else valid.reshape(b * s),
        scoring="sigmoid", choice_bias=p["router_bias"], form="relu2")
    with jax.named_scope(scopes.SHARED_EXPERT):
        shared = jnp.square(jax.nn.relu(h @ p["ws_up"])) @ p["ws_down"]
    stats = share_statistics(load, valid, b * s, cfg.top_k)
    with jax.named_scope(scopes.MOE_COMBINE):
        return x + routed.reshape(b, s, d) + shared, stats


def zero_state(cfg: NemotronHConfig, rows: int) -> tuple:
    """The state of ``rows`` sequences in one ``M`` layer before their
    first token."""
    return tuple(jnp.zeros((rows, *shape), dtype)
                 for _, shape, dtype in recurrent_state(cfg).arrays)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def forward(cfg: NemotronHConfig, params: dict, tokens):
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
            elif run.state is not None:
                x = x + recurrent_mixer(cfg, p, x, state, valid)[0]
            else:
                x, _ = feed_forward(cfg, p, x, valid=valid)
            return x, None

        x, _ = lax.scan(block, x, params["blocks"][run.key])
    x = rms_norm(x, params["final_norm"], eps=cfg.rms_eps)[:, :s]
    return head_logits(cfg, params, x.reshape(b * s, -1)).reshape(b, s, -1)
