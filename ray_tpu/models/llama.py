"""Llama-3-family transformer, TPU-first.

Flagship model of the framework (BASELINE.json config "Llama-3 8B/70B").
Design (deliberately NOT a port of any torch module tree):

- Pure functional: params are a pytree of arrays; a parallel pytree of
  *logical axis names* feeds ``ray_tpu.parallel.sharding`` so any strategy
  preset (fsdp / tp / fsdp_tp / fsdp_tp_sp) shards the same model without
  touching model code.
- All transformer blocks are stacked into single arrays with a leading
  ``layer`` axis and the forward pass runs ``lax.scan`` over them: one
  compiled block body regardless of depth (fast XLA compiles at 32-80
  layers), and the natural hook for per-layer rematerialization and
  pipeline-stage splitting.
- bf16 params/activations by default, fp32 for softmax/norm statistics and
  the final logits; matmuls via MXU with ``preferred_element_type=f32``
  where accuracy matters.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ray_tpu.ops import scopes
from ray_tpu.ops.attention import attention
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.rope import apply_rope, rope_sin_cos


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    d_ff: int = 14336
    head_dim: int = 128
    rope_theta: float = 500000.0
    rms_eps: float = 1e-5
    dtype: str = "bfloat16"
    # remat policy for the scan body:
    #   "none"      - save all activations (most HBM, no recompute)
    #   "full"      - save only layer inputs (least HBM, full recompute)
    #   "dots"      - save matmul outputs (recompute elementwise only)
    #   "attn"      - save only the attention OUTPUT: the backward never
    #                 re-runs the flash-attention forward — the known
    #                 lever for long-context MFU where attention
    #                 dominates (policy: save_only_these_names)
    #   "dots_attn" - dots + the attention output (skips both matmul and
    #                 flash-fwd recompute; elementwise-only recompute)
    remat: str = "full"
    tie_embeddings: bool = False

    @property
    def param_dtype(self):
        return jnp.dtype(self.dtype)


def llama3_8b() -> LlamaConfig:
    return LlamaConfig()


def llama3_70b() -> LlamaConfig:
    return LlamaConfig(d_model=8192, n_layers=80, n_heads=64, n_kv_heads=8,
                       d_ff=28672)


def llama_tiny(vocab_size: int = 512) -> LlamaConfig:
    """Test-size config: runs in seconds on the 8-device CPU mesh."""
    return LlamaConfig(
        vocab_size=vocab_size, d_model=128, n_layers=2, n_heads=4,
        n_kv_heads=2, d_ff=256, head_dim=32, remat="none",
    )


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def param_logical_axes(cfg: LlamaConfig) -> dict:
    """Logical axis annotation pytree, mirroring init_params' structure.
    The leading scan axis of stacked blocks carries the ``layers`` logical
    axis: replicated under dp/fsdp/tp presets (rules.layers=None) and
    sharded over ``pp`` under the pipeline-parallel preset, which makes the
    contiguous per-stage layer groups land on their stage's devices."""
    block = {
        "attn_norm": ("layers", "embed"),
        "wq": ("layers", "embed", "heads"),       # [L, D, H*hd]
        "wk": ("layers", "embed", "kv_heads"),
        "wv": ("layers", "embed", "kv_heads"),
        "wo": ("layers", "heads", "embed"),
        "mlp_norm": ("layers", "embed"),
        "w_gate": ("layers", "embed", "mlp"),
        "w_up": ("layers", "embed", "mlp"),
        "w_down": ("layers", "mlp", "embed"),
    }
    axes = {
        "embedding": ("vocab", "embed"),
        "blocks": block,
        "final_norm": ("embed",),
    }
    if not cfg.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


def fanin_init(key, shape, fan_in):
    """Fan-in-scaled normal init in fp32 (cast to param dtype at call sites).
    Shared by all model families."""
    scale = fan_in ** -0.5
    return jax.random.normal(key, shape, dtype=jnp.float32) * scale


def init_params(cfg: LlamaConfig, key) -> dict:
    """Initialize the parameter pytree (stacked-block layout)."""
    dt = cfg.param_dtype
    k_emb, k_blocks, k_head = jax.random.split(key, 3)
    d, l = cfg.d_model, cfg.n_layers
    qdim = cfg.n_heads * cfg.head_dim
    kvdim = cfg.n_kv_heads * cfg.head_dim

    def dense_init(key, shape, fan_in):
        return fanin_init(key, shape, fan_in).astype(dt)

    ks = jax.random.split(k_blocks, 7)
    blocks = {
        "attn_norm": jnp.ones((l, d), dtype=dt),
        "wq": dense_init(ks[0], (l, d, qdim), d),
        "wk": dense_init(ks[1], (l, d, kvdim), d),
        "wv": dense_init(ks[2], (l, d, kvdim), d),
        "wo": dense_init(ks[3], (l, qdim, d), qdim),
        "mlp_norm": jnp.ones((l, d), dtype=dt),
        "w_gate": dense_init(ks[4], (l, d, cfg.d_ff), d),
        "w_up": dense_init(ks[5], (l, d, cfg.d_ff), d),
        "w_down": dense_init(ks[6], (l, cfg.d_ff, d), cfg.d_ff),
    }
    params = {
        "embedding": dense_init(k_emb, (cfg.vocab_size, d), d),
        "blocks": blocks,
        "final_norm": jnp.ones((d,), dtype=dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(k_head, (d, cfg.vocab_size), d)
    return params


def num_params(params) -> int:
    return sum(p.size for p in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def attention_projections(cfg, p, x, sin, cos):
    """What attention takes in, from the residual stream ``x`` [b, s, d]:
    pre-norm, the three projections split into heads, rotary on ``q`` and
    ``k``. Returns (q [b, s, heads, hd], k, v [b, s, kv heads, hd]). With
    ``feed_forward`` the block as two pieces that take no view on where
    keys and values live: ``attention_sublayer`` puts causal attention
    over the sequence between them, the paged serving engine its page
    pool (``serve/paged_llm.py``). Where the layer's weights hold the
    fused ``wqkv`` (``fuse_attention_projections``) the three are one
    matmul, split afterwards: each output column is the dot product it
    was."""
    b, s, _ = x.shape
    with jax.named_scope(scopes.ATTN_QKV):
        h = rms_norm(x, p["attn_norm"], eps=cfg.rms_eps)
        if "wqkv" in p:
            qdim = cfg.n_heads * cfg.head_dim
            kvdim = cfg.n_kv_heads * cfg.head_dim
            q, k, v = (y.reshape(b, s, -1, cfg.head_dim) for y in jnp.split(
                h @ p["wqkv"], [qdim, qdim + kvdim], axis=-1))
        else:
            q = (h @ p["wq"]).reshape(b, s, cfg.n_heads, cfg.head_dim)
            k = (h @ p["wk"]).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
            v = (h @ p["wv"]).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
        return apply_rope(q, sin, cos), apply_rope(k, sin, cos), v


def attention_output(cfg, p, x, attn):
    """The attention sublayer's end: the heads' outputs ``attn`` ([b, s,
    heads, hd], or [b, heads, hd] of a one-token step) through ``wo``,
    added to the residual stream ``x`` [b, s, d]. The third of the block's
    pieces that the serving engine calls (a block that gates its heads
    does it here)."""
    b, s, _ = x.shape
    with jax.named_scope(scopes.ATTN_OUT):
        return x + attn.reshape(b, s, -1) @ p["wo"]


class LayerStack(NamedTuple):
    """A run of identical layers, as the serving engine's layer loop
    takes it (``layer_plan``): where its weights are, and what one of its
    layers HOLDS per sequence (pages, in the format ``rows`` names, or
    none; recurrent state or none) and DOES (attention over those pages,
    a recurrent mixer, a feed-forward: each or not). The defaults are a
    transformer block's: K/V pages and attention, then a feed-forward.
    The engine's stores have as many layers as the plan has layers that
    keep them, and no more."""
    key: str | None      # ``params["blocks"][key]`` holds the run's weights
    #                      stacked on a leading axis; None: the blocks do
    kind: str            # which of ``rotary_tables`` its attention takes
    window: int | None   # keys a query sees (a sliding layer); None: all
    layers: int
    # what a sequence keeps per layer of the run BESIDE its pages, where
    # the layers hold a recurrent mixer (``models/falcon_h1.py:
    # RecurrentState``: the arrays' shapes and dtypes, the scan's chunk):
    # beside the attention on the same input where ``attends``, else the
    # layer's one sublayer; None: no mixer and no state
    state: tuple | None = None
    # what a token keeps in a page of a layer of the run, where that is
    # not a K row and a V row a KV head: the rows, one pool each
    # (``ops/paged_attention.py:PageRow``: name, width, dtype), as a
    # latent-attention layer's compressed row and its indexer's key;
    # None: the K/V twins
    rows: tuple | None = None
    # keys a query attends over at most, where the layers pick them (a
    # learned selection: the rows, or the rows ``beside`` the K/V twins,
    # then hold an index key); None: all it may see
    selects: int | None = None
    # whether the layers attend: project, write a page, read the pages.
    # False: they keep NO pages (``kind``, ``window``, ``rows`` and
    # ``selects`` then say nothing)
    attends: bool = True
    # whether the layers end in a feed-forward
    feeds: bool = True
    # whether that feed-forward takes something of the layer's INPUT, the
    # stream BEFORE the attention (a router that reads the rows the
    # attention's projections read): the module's ``feed_ahead(cfg, p,
    # x)`` is then called where the layer begins and what it gives is
    # handed to ``feed_forward`` as ``ahead=`` where the layer ends.
    # False: the feed-forward sees the stream behind the attention alone
    ahead: bool = False
    # further rows a token keeps in a page of a layer of the run BESIDE
    # its K/V twins (``rows`` is then None), one pool each behind the
    # twins' four (``PageRow``s, as ``rows``): an indexer's key, where
    # the layers pick the keys they attend over (``selects``) among K/V
    # rows; the module's ``index_projections`` then gives what the
    # indexer takes of a layer's tokens. None: the twins alone
    beside: tuple | None = None


def layer_plan(cfg) -> tuple:
    """The model's layers as runs of identical layers, in order. Here one
    run: every layer is the same block, stacked in ``params["blocks"]``,
    and attends over its whole context."""
    return (LayerStack(None, "full", None, cfg.n_layers),)


def rotary_tables(cfg, positions) -> dict:
    """(sin, cos) of ``positions`` for each kind of layer the plan names."""
    return {"full": rope_sin_cos(positions, cfg.head_dim,
                                 theta=cfg.rope_theta)}


def fuse_attention_projections(blocks):
    """The stacked blocks with ``wq``, ``wk`` and ``wv`` as ONE stack
    ``wqkv`` [layers, d, (heads + 2 x kv heads) x hd], columns q | k | v,
    which ``attention_projections`` takes in their place. For a program
    that scans the layers many times over the same weights (the serving
    engine's decode program), called once at the program's entry: a
    stack of one projection can be small enough for the compiler to park
    on the core (``wk`` at 12 layers of Mistral-7B's widths is 100.7 MB
    of a v5e's 128 MiB) and then to write back and refetch WHOLE round
    every layer's attention kernel; the fused stack (604 MB there) cannot
    be, and a layer reads its own slice of it where it lies."""
    blocks = dict(blocks)
    with jax.named_scope(scopes.ATTN_QKV):
        blocks["wqkv"] = jnp.concatenate(
            [blocks.pop("wq"), blocks.pop("wk"), blocks.pop("wv")], axis=-1)
    return blocks


def feed_forward(cfg, p, x, valid=None):
    """Pre-norm SwiGLU feed-forward over ``x`` [b, s, d]; returns (the
    residual-added stream, its statistics: a dense block has none).
    ``valid`` [b, s] marks the rows that are tokens, for a block that
    routes; a dense one computes every row."""
    with jax.named_scope(scopes.FFN):
        h = rms_norm(x, p["mlp_norm"], eps=cfg.rms_eps)
        gated = jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])
        return x + gated @ p["w_down"], {}


def attention_sublayer(cfg, x, p, sin, cos, segment_ids, attn_impl,
                       mesh=None, sp_axis="sp"):
    """Pre-norm attention sublayer (shared by Llama and Mixtral blocks).
    Returns the residual-added stream."""
    b, s, d = x.shape
    q, k, v = attention_projections(cfg, p, x, sin, cos)
    if attn_impl == "ring":
        from ray_tpu.parallel.ring_attention import ring_attention

        if mesh is None:
            raise ValueError(
                "attn_impl='ring' requires mesh= (and an sp mesh axis)"
            )
        if segment_ids is not None:
            raise ValueError("ring attention does not support segment_ids yet")
        with jax.named_scope(scopes.ATTN):
            attn_out = ring_attention(q, k, v, mesh=mesh, axis=sp_axis,
                                      causal=True)
    else:
        attn_out = attention(q, k, v, causal=True, segment_ids=segment_ids,
                             impl=attn_impl, mesh=mesh)
    # checkpoint naming for the "attn"/"dots_attn" remat policies lives
    # INSIDE the attention impls (flash names its kernel residuals in
    # _flash_vjp_fwd; the reference impl names its output in
    # ops/attention.py) — naming the post-reshape copy here too would
    # double-store ~b*s*d per layer under those policies.
    with jax.named_scope(scopes.ATTN_OUT):
        attn_out = attn_out.reshape(b, s, cfg.n_heads * cfg.head_dim)
        return x + attn_out @ p["wo"]


def _block(cfg: LlamaConfig, x, layer_params, sin, cos, segment_ids,
           attn_impl, mesh=None, sp_axis="sp"):
    """One transformer block: pre-norm attention + SwiGLU MLP."""
    p = layer_params
    x = attention_sublayer(cfg, x, p, sin, cos, segment_ids, attn_impl,
                           mesh=mesh, sp_axis=sp_axis)
    x, _ = feed_forward(cfg, p, x)
    return x


def forward(
    cfg: LlamaConfig,
    params: dict,
    tokens,             # [batch, seq] int32
    *,
    positions=None,     # [batch, seq] int32 (defaults to arange)
    segment_ids=None,   # [batch, seq] for packed sequences
    attn_impl: str = "auto",
    mesh=None,          # required for attn_impl="ring" (sequence parallel)
    sp_axis: str = "sp",
):
    """Token ids -> logits [batch, seq, vocab] (fp32)."""
    x = forward_hidden(cfg, params, tokens, positions=positions,
                       segment_ids=segment_ids, attn_impl=attn_impl,
                       mesh=mesh, sp_axis=sp_axis)
    with jax.named_scope(scopes.LM_HEAD):
        return jnp.einsum("bsd,dv->bsv", x, lm_head_weights(cfg, params),
                          preferred_element_type=jnp.float32)


def forward_hidden(cfg, params, tokens, *, positions=None,
                   segment_ids=None, attn_impl="auto", mesh=None,
                   sp_axis="sp"):
    """Token ids -> final normalized hidden states [b, s, d] (the input
    to the LM head). Split out so losses can fuse the head projection."""
    b, s = tokens.shape
    x = embed(cfg, params, tokens)
    if positions is None:
        positions = jnp.arange(s, dtype=jnp.int32)[None, :]
    sin, cos = rotary_tables(cfg, positions)["full"]

    body = partial(_block, cfg, sin=sin, cos=cos, segment_ids=segment_ids,
                   attn_impl=attn_impl, mesh=mesh, sp_axis=sp_axis)
    if cfg.remat == "full":
        body = jax.checkpoint(body)
    elif cfg.remat == "dots":
        body = jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
        )
    elif cfg.remat == "attn":
        # "attn_lse" must be saved WITH the output: both are flash-bwd
        # residuals — with them saved, remat DCE drops the flash-forward
        # call from the backward entirely
        body = jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.save_only_these_names(
                "attn_out", "attn_lse"),
        )
    elif cfg.remat == "dots_attn":
        body = jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.save_from_both_policies(
                jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
                jax.checkpoint_policies.save_only_these_names(
                    "attn_out", "attn_lse"),
            ),
        )

    def scan_fn(x, layer_params):
        return body(x, layer_params), None

    x, _ = lax.scan(scan_fn, x, params["blocks"])
    return rms_norm(x, params["final_norm"], eps=cfg.rms_eps)


def embed(cfg, params, tokens):
    """Token ids -> the residual stream's start (a model that scales its
    embedding states its own)."""
    with jax.named_scope(scopes.EMBED):
        return params["embedding"][tokens]


def head_logits(cfg, params, x):
    """Normed last hidden states [b, d] -> float32 logits [b, vocab]."""
    with jax.named_scope(scopes.LM_HEAD):
        return jnp.einsum("bd,dv->bv", x, lm_head_weights(cfg, params),
                          preferred_element_type=jnp.float32)


def lm_head_weights(cfg, params):
    """The LM head matrix [d, vocab] honoring tie_embeddings — the ONE
    place tied-embedding semantics live."""
    return (params["embedding"].T if cfg.tie_embeddings
            else params["lm_head"])


def fused_cross_entropy(cfg, params, hidden, targets, *, mask=None,
                        chunk: int = 1024, z_loss: float = 0.0,
                        mesh=None, rows=(), vocab_axes=()):
    """CE loss WITHOUT materializing the full [b, s, vocab] fp32 logits
    (2+ GB at 8x2048x32k): the LM-head matmul + logsumexp run per
    sequence chunk inside a checkpointed scan, so peak memory is one
    chunk of logits and the backward recomputes them. This is the
    standard fused-softmax-xent trade: ~2x head FLOPs for ~vocab/chunk x
    less logits HBM traffic.

    hidden: [b, s, d] from forward_hidden; targets [b, s] int; mask
    [b, s] in {0,1}.

    ``vocab_axes`` (with ``mesh`` and ``rows``, the mesh axes that split b
    and those that split s: what ``parallel.sharding.loss_layout`` reads
    from the mesh and the rules) splits the vocabulary over those mesh
    axes inside the loss: see ``_vocab_split_cross_entropy``. Left to the
    partitioner under fsdp (the head split on d, the axis the logits
    contract over), every chunk's fp32 logits are all-reduced, forward
    and backward: 12.6% of the four-chip step's device time, and the
    step 15% slower (PERF.md, PR 39). On one chip at a 32k vocabulary the
    recompute costs 3.4% against the dense loss (same place).
    """
    with jax.named_scope(scopes.LOSS):
        head = lm_head_weights(cfg, params)
        if vocab_axes:
            if mask is None:
                mask = targets >= 0
            return _vocab_split_cross_entropy(
                head, hidden, jnp.maximum(targets, 0),
                mask.astype(jnp.float32), chunk=chunk, z_loss=z_loss,
                mesh=mesh, rows=rows, vocab_axes=vocab_axes)
        b, s, d = hidden.shape
        n = b * s
        xm = hidden.reshape(n, d)
        tg = jnp.maximum(targets.reshape(n), 0)
        # mask=None derives the mask from the -1 padding convention (same
        # contract as the trainer's dense path) — silently averaging padding
        # in as class-0 predictions would be a wrong loss with no error
        mk = ((targets.reshape(n) >= 0).astype(jnp.float32) if mask is None
              else mask.reshape(n).astype(jnp.float32))
        # pad to a whole number of chunks (padding masked out)
        pad = (-n) % chunk
        if pad:
            xm = jnp.concatenate([xm, jnp.zeros((pad, d), xm.dtype)])
            tg = jnp.concatenate([tg, jnp.zeros((pad,), tg.dtype)])
            mk = jnp.concatenate([mk, jnp.zeros((pad,), mk.dtype)])
        n_chunks = (n + pad) // chunk
        xc = xm.reshape(n_chunks, chunk, d)
        tc = tg.reshape(n_chunks, chunk)
        mc = mk.reshape(n_chunks, chunk)

        def body(carry, inp):
            x_i, t_i, m_i = inp
            logits = jnp.einsum("cd,dv->cv", x_i, head,
                                preferred_element_type=jnp.float32)
            lse = jax.nn.logsumexp(logits, axis=-1)
            tl = jnp.take_along_axis(logits, t_i[:, None], axis=1).squeeze(-1)
            nll = lse - tl
            if z_loss > 0.0:
                nll = nll + z_loss * jnp.square(lse)
            total, count = carry
            return (total + jnp.sum(nll * m_i), count + jnp.sum(m_i)), None

        (total, count), _ = lax.scan(
            jax.checkpoint(body), (jnp.float32(0.0), jnp.float32(0.0)),
            (xc, tc, mc))
        return total / jnp.maximum(count, 1.0)


def _vocab_split_cross_entropy(head, hidden, targets, mask, *, chunk, z_loss,
                               mesh, rows, vocab_axes):
    """``fused_cross_entropy`` with the vocabulary split over ``vocab_axes``
    and the rows ([b, s], split over ``rows``' axes) gathered chunk by chunk:
    every device takes each chunk whole against its own [d, vocab / n]
    slice of the head. The same fp32 logits for every row and id as on one
    device, the same masked mean; only the order of the sums differs.

    What crosses the mesh: the head, resharded once a step (and its
    gradient back); a chunk's rows, gathered in their own type; three fp32
    vectors a chunk (the rows' maxima, their sums of exponentials with the
    targets' logits, the backward's cotangent of the rows' losses); and the
    chunk's hidden cotangent, summed over the devices in fp32, each device
    keeping its own rows, and rounded once."""
    row_axes = tuple(a for axes in rows for a in axes)
    rows = P(*(axes or None for axes in rows))
    vocab_only = tuple(a for a in vocab_axes if a not in row_axes)
    # a device's rows of a chunk
    c_loc = max(1, chunk // math.prod(mesh.shape[a] for a in row_axes))

    @jax.custom_vjp
    def chunk_logits(x, w):
        return chunk_logits_fwd(x, w)[0]

    def chunk_logits_fwd(x, w):
        x = lax.all_gather(x, row_axes, axis=0, tiled=True)
        return jnp.einsum("cd,dv->cv", x, w,
                          preferred_element_type=jnp.float32), (x, w)

    def chunk_logits_bwd(res, g):
        # autodiff would round each device's partial cotangent to the
        # rows' type BEFORE the sum over the devices
        x, w = res
        dx = lax.dot_general(g, w, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        dx = lax.psum_scatter(dx, row_axes, scatter_dimension=0, tiled=True)
        if vocab_only:
            dx = lax.psum(dx, vocab_only)
        dw = lax.dot_general(x, g, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
        return dx.astype(x.dtype), dw.astype(w.dtype)

    chunk_logits.defvjp(chunk_logits_fwd, chunk_logits_bwd)

    def local_loss(head, hidden, targets, mask):
        d = hidden.shape[-1]
        pad = (-hidden.shape[0] * hidden.shape[1]) % c_loc

        def chunks(a, *tail):      # the device's rows, masked padding last
            a = a.reshape(-1, *tail)
            a = jnp.concatenate([a, jnp.zeros((pad, *tail), a.dtype)])
            return a.reshape(-1, c_loc, *tail)

        xc, mc = chunks(hidden, d), chunks(mask)
        tc = lax.all_gather(chunks(targets), row_axes, axis=1, tiled=True)
        first_id = lax.axis_index(vocab_axes) * head.shape[1]
        first_row = lax.axis_index(row_axes) * c_loc

        def body(carry, inp):
            x_i, t_i, m_i = inp
            logits = chunk_logits(x_i, head)
            top = lax.pmax(lax.stop_gradient(logits.max(axis=-1)),
                           vocab_axes)
            ids = lax.broadcasted_iota(jnp.int32, logits.shape, 1)
            sums, tl = lax.psum(
                (jnp.exp(logits - top[:, None]).sum(axis=-1),
                 jnp.where(ids == (t_i - first_id)[:, None], logits,
                           0.0).sum(axis=-1)), vocab_axes)
            lse = top + jnp.log(sums)
            nll = lse - tl
            if z_loss > 0.0:
                nll = nll + z_loss * jnp.square(lse)
            # every device holds the chunk's losses: each sums its own rows
            nll = lax.dynamic_slice_in_dim(nll, first_row, c_loc)
            total, count = carry
            return (total + jnp.sum(nll * m_i), count + jnp.sum(m_i)), None

        zero = lax.pcast(jnp.float32(0.0), row_axes, to="varying")
        (total, count), _ = lax.scan(jax.checkpoint(body), (zero, zero),
                                     (xc, tc, mc))
        total, count = lax.psum((total, count), row_axes)
        return total / jnp.maximum(count, 1.0)

    return jax.shard_map(
        local_loss, mesh=mesh,
        in_specs=(P(None, vocab_axes), P(*rows, None), rows, rows),
        out_specs=P())(head, hidden, targets, mask)


def cross_entropy_loss(logits, targets, *, mask=None, z_loss: float = 0.0):
    """Token-level CE in fp32 with optional z-loss regularizer.

    ``mask`` [batch, seq] in {0,1} excludes padding from the mean.
    """
    with jax.named_scope(scopes.LOSS):
        logits = logits.astype(jnp.float32)
        logsumexp = jax.nn.logsumexp(logits, axis=-1)
        target_logit = jnp.take_along_axis(
            logits, targets[..., None], axis=-1
        ).squeeze(-1)
        nll = logsumexp - target_logit
        if z_loss > 0.0:
            nll = nll + z_loss * jnp.square(logsumexp)
        if mask is not None:
            mask = mask.astype(jnp.float32)
            return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        return jnp.mean(nll)
