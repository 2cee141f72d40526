"""Latent-attention sparse-expert decoders with a learned key selection,
served by ``ray_tpu.models.dots3_note`` (dots-studio dots3-note-prev,
``model_type`` ``dots3_note``, the text path of the language model): the
adapter from the published Hugging Face keys to the program's config
class, the plain reference of the block, and its operation and byte counts
(``benchmark/families/__init__.py`` says what a family is).

The reference follows the published ``config.json``, layer by layer
(``layer_types``). ``d`` = ``hidden_size``, RMSNorm in float32. Every
layer: ``u = rms(h, attn_norm)``; the query latent ``cq = rms(u Wqa,
q_norm) x sq``, ``q = cq Wqb`` in heads of ``qk_nope_head_dim +
qk_rope_head_dim``, the last part rotated; the KV latent ``ckv = rms(u
Wkva[:r], kv_norm) x skv`` and ONE rotary key ``kr = rope(u Wkva[r:])``
for all heads; ``kn | v = ckv Wkvb`` by head; score ``(qn . kn + qr . kr)
/ sqrt(dn + dr)``; causal softmax over the keys the layer lets a query
see; each head's output times its gate; ``Wo``. A ``full_attention``
layer (``num_attention_heads``, ``q_lora_rank``, ``kv_lora_rank``,
``rope_theta``) lets query t see the ``index_topk`` keys of largest
``I(t, s) = sum_j w_j relu(qI_j(t) . kI(s))`` among s <= t, ties to the
lower position (``qI = cq WIq`` in ``index_n_heads`` heads of
``index_head_dim``, ``kI = layernorm(u WIk)``, ``w = u WIw``, rotary at
``rope_theta`` over the first ``qk_rope_head_dim`` numbers of each ``qI_j``
and of ``kI``); a ``sliding_attention`` layer (the ``swa_`` keys) the
``sliding_window_size`` newest, its own among them. Then the first
``first_k_dense_replace`` layers a dense SwiGLU, every other layer the
routed experts (sigmoid scores over all the router's experts, the
``num_experts_per_tok`` of largest score + correction bias, the chosen
scores divided by their sum + 1e-20, times ``routed_scaling_factor``)
plus the shared expert; final RMSNorm; untied head.

What ``config.json`` names and does not define is a NAMED DEPARTURE of
``logits``, with the reading taken as its default, so that the other
reading is one argument away (the configuration file lists each under
``assumed``):

- ``rescale=True``: ``apply_mla_qkv_lora_rescale`` is ``sq = sqrt(d /
  q_lora_rank)`` on the normed query latent and ``skv = sqrt(d /
  kv_lora_rank)`` on the normed KV latent, the rotary key left alone (what
  ``transformers``' ``longcat_flash`` does under ``mla_scale_q_lora`` /
  ``mla_scale_kv_lora``); ``rescale=False`` leaves both out;
- ``gate="sigmoid"``: ``attention_gate_type: headwise`` is ``sigmoid(u
  Wg)``, ``Wg [d, heads]``, no bias, on each head's output before ``Wo``
  (arXiv:2505.06708); ``gate="none"`` leaves it out;
- ``window="published"``: ``sliding_window_size`` keys with the query's
  own among them; ``window=None`` lets a sliding layer see everything;
- ``indexer="learned"``: the selection above, DeepSeek-V3.2's (its
  Hadamard rotation of ``qI`` and ``kI`` is orthogonal and its FP8
  rounding a precision this configuration does not state: both left
  out); ``indexer="none"`` lets a full layer see every key; ``topk=``
  another count than ``index_topk``;
- ``scores="sigmoid"``, ``bias=True``: no ``n_group`` / ``topk_group`` key,
  so one group; ``scores="softmax"`` scores by a softmax over the
  router's logits, ``bias=False`` chooses by the score alone.

THE SHARE. ``n_routed_experts`` in a configuration file is the number of
experts HELD here, of ``expert_share.num_experts_total`` that the router
scores, starting at ``expert_share.index x n_routed_experts``: the
reference routes over all of them and adds the held experts' part, as the
program does and as one chip of an expert-parallel group would before the
exchange. ``vocab_size`` is likewise the slice held here.

It reads the program's parameter layout, which is data, not code
(``params["blocks"]`` maps ``layers<first>[-<last>]`` to that run of
identical layers' weights stacked on a leading axis; ``w_in`` holds the
columns query latent | KV latent | rotary key | gate and, in a full layer,
index key | index weights), and imports nothing from the program. It
computes the expanded form only, a layer at a time, attention a block of
queries at a time (the scores, the indexer's scores and the selection
mask of a block are all that exists at once), the experts as a masked
loop over the held ones, the head in column blocks. On a TPU a float32
matrix multiplication runs in lower precision unless told otherwise:
``logits`` runs under ``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp

QUERY_BLOCK = 256       # queries whose scores exist at once
HEAD_BLOCK = 8192       # columns of the head at once
INDEX_NORM_EPS = 1e-6   # the index key's LayerNorm (torch's default)


# -- the adapter: the one part that touches the program -----------------

def _share(config: dict) -> tuple:
    """(experts the router scores, index of the first one held here)."""
    share = config.get("expert_share")
    if share is None:
        return config["n_routed_experts"], 0
    return (share["num_experts_total"],
            share["index"] * config["n_routed_experts"])


def model_config(config: dict):
    from ray_tpu.models import dots3_note

    total, first = _share(config)
    return dots3_note.Dots3NoteConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        layer_types=tuple(config["layer_types"]),
        mlp_only_layers=tuple(range(config["first_k_dense_replace"])),
        q_rank=config["q_lora_rank"], rope_dim=config["qk_rope_head_dim"],
        v_dim=config["v_head_dim"], n_heads=config["num_attention_heads"],
        kv_rank=config["kv_lora_rank"], nope_dim=config["qk_nope_head_dim"],
        rope_theta=float(config["rope_theta"]),
        index_heads=config["index_n_heads"],
        index_dim=config["index_head_dim"], index_topk=config["index_topk"],
        n_heads_sliding=config["swa_num_attention_heads"],
        kv_rank_sliding=config["swa_kv_lora_rank"],
        nope_dim_sliding=config["swa_qk_nope_head_dim"],
        rope_theta_sliding=float(config["swa_rope_theta"]),
        window=config["sliding_window_size"],
        rescale=bool(config["apply_mla_qkv_lora_rescale"]),
        d_ff=config["intermediate_size"],
        d_expert=config["moe_intermediate_size"],
        d_shared=config["moe_intermediate_size"] * config["n_shared_experts"],
        n_experts=total, n_experts_held=config["n_routed_experts"],
        first_expert=first, top_k=config["num_experts_per_tok"],
        norm_topk_prob=config["norm_topk_prob"],
        routed_scale=float(config["routed_scaling_factor"]),
        rms_eps=float(config["rms_norm_eps"]),
        dtype={"bfloat16": "bfloat16", "float32": "float32"}[
            config.get("torch_dtype", "bfloat16")],
        tie_embeddings=config["tie_word_embeddings"])


def init_params(model_cfg, key):
    from ray_tpu.models import dots3_note

    return dots3_note.init_params(model_cfg, key)


# -- the plain reference -------------------------------------------------

def _shape(m: dict, sliding: bool) -> tuple:
    """(heads, query rank, KV rank, no-position width, rotary width, value
    width, rotary base) of a layer's attention."""
    pre = "swa_" if sliding else ""
    return (m[pre + "num_attention_heads"], m[pre + "q_lora_rank"],
            m[pre + "kv_lora_rank"], m[pre + "qk_nope_head_dim"],
            m[pre + "qk_rope_head_dim"], m[pre + "v_head_dim"],
            float(m[pre + "rope_theta"]))


def _rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rope(x, positions, base):
    """x [s, ..., r]: rotate pairs (i, i + r/2) of the last axis by the
    position's angle (rotate-half)."""
    r = x.shape[-1]
    inv_freq = 1.0 / base ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = positions[:, None].astype(jnp.float32) * inv_freq
    ang = ang.reshape(ang.shape[0], *([1] * (x.ndim - 2)), r // 2)
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., :r // 2], x[..., r // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def _selected(scores, seen, topk):
    """[q, s] bool: for each query the ``topk`` seen keys of largest
    score, ties to the lower position; every seen key where there are no
    more than ``topk``."""
    order = jnp.argsort(jnp.where(seen, -scores, jnp.inf), axis=-1,
                        stable=True)
    rank = jnp.argsort(order, axis=-1)
    return seen & (rank < topk)


def _attention(u, p, positions, *, shape, eps, rescale, gate, window,
               index):
    """One sequence's attention sublayer, u [s, d] the normed input:
    float32 [s, heads x dv] before ``Wo``. ``index``: None, or (heads,
    width, topk) of the layer's indexer."""
    heads, rq, r, dn, dr, dv, base = shape
    f32 = lambda name: p[name].astype(jnp.float32)   # noqa: E731
    s, d = u.shape
    y = u @ f32("w_in")
    cq = _rms_norm(y[:, :rq], f32("q_norm"), eps)
    ckv = _rms_norm(y[:, rq:rq + r], f32("kv_norm"), eps)
    if rescale:
        cq, ckv = cq * (d / rq) ** 0.5, ckv * (d / r) ** 0.5
    q = (cq @ f32("wq_b")).reshape(s, heads, dn + dr)
    qn, qr = q[..., :dn], _rope(q[..., dn:], positions, base)
    at = rq + r
    kr = _rope(y[:, at:at + dr], positions, base)
    g = y[:, at + dr:at + dr + heads]
    kv = (ckv @ f32("wkv_b")).reshape(s, heads, dn + dv)
    kn, v = kv[..., :dn], kv[..., dn:]
    if index is not None:
        hi, di, topk = index
        at += dr + heads
        qi = (cq @ f32("wi_q")).reshape(s, hi, di)
        qi = jnp.concatenate(
            [_rope(qi[..., :dr], positions, base), qi[..., dr:]], -1)
        ki = y[:, at:at + di]
        mean = jnp.mean(ki, axis=-1, keepdims=True)
        ki = ((ki - mean) * jax.lax.rsqrt(
            jnp.mean(jnp.square(ki - mean), axis=-1, keepdims=True)
            + INDEX_NORM_EPS) * f32("index_norm") + f32("index_norm_bias"))
        ki = jnp.concatenate(
            [_rope(ki[:, :dr], positions, base), ki[:, dr:]], -1)
        wi = y[:, at + di:at + di + hi]

    def block(first):
        rows = first + jnp.arange(QUERY_BLOCK)
        rows = jnp.minimum(rows, s - 1)            # the last block's tail
        i, j = positions[rows][:, None], positions[None, :]
        seen = j <= i
        if window is not None:
            seen = seen & (j > i - window)
        if index is not None:
            dots = jnp.einsum("qhd,sd->qhs", qi[rows], ki)
            scores = jnp.sum(wi[rows][:, :, None] * jax.nn.relu(dots), 1)
            seen = _selected(scores, seen, topk)
        att = (jnp.einsum("qhd,shd->hqs", qn[rows], kn)
               + jnp.einsum("qhd,sd->hqs", qr[rows], kr)) * (dn + dr) ** -0.5
        att = jax.nn.softmax(jnp.where(seen[None], att, -jnp.inf), axis=-1)
        return jnp.einsum("hqs,shd->qhd", att, v)

    firsts = jnp.arange(0, s, QUERY_BLOCK)
    out = jax.lax.map(block, firsts).reshape(-1, heads, dv)[:s]
    # (a tail block repeats the last row; the slice drops the repeats)
    if gate == "sigmoid":
        out = out * jax.nn.sigmoid(g)[..., None]
    return out.reshape(s, heads * dv)


def _routed_ffn(h, p, *, top_k, norm_topk_prob, routing_scale, scores, bias,
                first):
    """h [s, d] float32 -> the HELD routed experts' sum, float32: the
    router scores every expert; experts ``first`` onwards, as many as the
    stacks hold, add their part."""
    logits = h @ p["router"].astype(jnp.float32)
    score = (jax.nn.sigmoid(logits) if scores == "sigmoid"
             else jax.nn.softmax(logits, axis=-1))
    choice = score + p["router_bias"].astype(jnp.float32) if bias else score
    kth = jnp.sort(choice, axis=-1)[..., -top_k]
    weight = jnp.where(choice >= kth[..., None], score, 0.0)     # [s, E]
    chosen = choice >= kth[..., None]
    if norm_topk_prob:
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
    weight = weight * routing_scale
    held = p["wi_gate"].shape[0]
    weight, chosen = (a[..., first:first + held] for a in (weight, chosen))

    def one_expert(y, expert):
        gate, up, down, w, on = expert
        out = _swiglu(h, *(a.astype(jnp.float32) for a in (gate, up, down)))
        return y + jnp.where(on[..., None], w[..., None] * out, 0.0), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                        (p["wi_gate"], p["wi_up"], p["wo_e"],
                         jnp.moveaxis(weight, -1, 0),
                         jnp.moveaxis(chosen, -1, 0)))
    return y


@functools.partial(jax.jit, static_argnames=(
    "shape", "eps", "rescale", "gate", "window", "index", "top_k",
    "norm_topk_prob", "routing_scale", "scores", "bias", "first"))
def _layer(x, p, *, shape, eps, rescale, gate, window, index, top_k,
           norm_topk_prob, routing_scale, scores, bias, first):
    """One decoder layer on x [b, s, d] float32; p holds this layer's
    weights in their stored dtype."""
    f32 = lambda name: p[name].astype(jnp.float32)   # noqa: E731
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)
    u = _rms_norm(x, f32("attn_norm"), eps)
    attend = functools.partial(
        _attention, p=p, positions=positions, shape=shape, eps=eps,
        rescale=rescale, gate=gate, window=window, index=index)
    x = x + jax.lax.map(attend, u) @ f32("wo")       # a sequence at a time
    h = _rms_norm(x, f32("mlp_norm"), eps)
    if "w_gate" in p:
        return x + _swiglu(h, f32("w_gate"), f32("w_up"), f32("w_down"))
    routed = jax.lax.map(functools.partial(
        _routed_ffn, p=p, top_k=top_k, norm_topk_prob=norm_topk_prob,
        routing_scale=routing_scale, scores=scores, bias=bias,
        first=first), h)
    return x + routed + _swiglu(h, f32("ws_gate"), f32("ws_up"),
                                f32("ws_down"))


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, lm_head, *, eps):
    x = _rms_norm(x, final_norm.astype(jnp.float32), eps)
    cols = lm_head.shape[1]
    return jnp.concatenate(
        [x @ lm_head[:, c:c + HEAD_BLOCK].astype(jnp.float32)
         for c in range(0, cols, HEAD_BLOCK)], axis=-1)


@jax.jit
def _embed(embedding, tokens):
    return embedding[tokens].astype(jnp.float32)


def _layers(blocks: dict):
    """Each layer's weights in layer order, from the runs' stacks."""
    first = lambda key: int(re.match(r"layers(\d+)", key).group(1))  # noqa: E731
    for key in sorted(blocks, key=first):
        stack = blocks[key]
        for i in range(jax.tree.leaves(stack)[0].shape[0]):
            yield jax.tree.map(lambda a: a[i], stack)


def logits(config: dict, params: dict, tokens, *, rescale=None,
           gate="sigmoid", window="published", indexer="learned", topk=None,
           scores="sigmoid", bias=True) -> jax.Array:
    """Float32 logits [b, s, vocab] of ``tokens`` [b, s], one layer at a
    time. The keyword arguments are the named departures of the module
    docstring; their defaults are the configuration's reading."""
    if rescale is None:
        rescale = bool(config["apply_mla_qkv_lora_rescale"])
    if window == "published":
        window = config["sliding_window_size"]
    index = None if indexer == "none" else (
        config["index_n_heads"], config["index_head_dim"],
        config["index_topk"] if topk is None else topk)
    kw = dict(eps=float(config["rms_norm_eps"]), rescale=rescale, gate=gate,
              top_k=config["num_experts_per_tok"],
              norm_topk_prob=bool(config["norm_topk_prob"]),
              routing_scale=float(config["routed_scaling_factor"]),
              scores=scores, bias=bias, first=_share(config)[1])
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embedding"], tokens)
        for kind, p in zip(config["layer_types"],
                           _layers(params["blocks"]), strict=True):
            sliding = kind == "sliding_attention"
            x = _layer(x, p, shape=_shape(config, sliding),
                       window=window if sliding else None,
                       index=None if sliding else index, **kw)
        head = (params["embedding"].T if config["tie_word_embeddings"]
                else params["lm_head"])
        return _head(x, params["final_norm"], head, eps=kw["eps"])


# -- the counts ----------------------------------------------------------

def attention_layer_counts(m: dict) -> tuple:
    """(full layers, sliding layers)."""
    sliding = sum(t == "sliding_attention" for t in m["layer_types"])
    return len(m["layer_types"]) - sliding, sliding


def _indexer_params(m: dict) -> int:
    """A full layer's indexer: queries from the query latent, the key
    and the head weights from the hidden state, the key's LayerNorm."""
    hi, di = m["index_n_heads"], m["index_head_dim"]
    return (m["q_lora_rank"] * hi * di + m["hidden_size"] * (di + hi)
            + 2 * di)


def attention_params(m: dict, sliding: bool) -> int:
    """A layer's attention weights: both latents' down-projections, the
    shared rotary key's and the gate's, the latents' norms, the two
    expansions, ``wo`` and, in a full layer, the indexer."""
    heads, rq, r, dn, dr, dv, _ = _shape(m, sliding)
    d = m["hidden_size"]
    return (d * (rq + r + dr + heads) + rq + r
            + rq * heads * (dn + dr) + r * heads * (dn + dv)
            + heads * dv * d + (0 if sliding else _indexer_params(m)))


def expert_params(m: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def shared_expert_params(m: dict) -> int:
    return expert_params(m) * m["n_shared_experts"]


def router_params(m: dict) -> int:
    """The router and its correction bias, both float32."""
    return (m["hidden_size"] + 1) * _share(m)[0]


def dense_mlp_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def _sparse_layers(m: dict) -> int:
    return len(m["layer_types"]) - m["first_k_dense_replace"]


def total_params(m: dict) -> int:
    """Parameters HELD here: ``n_routed_experts`` experts a sparse layer,
    the ``vocab_size`` rows of the embedding and columns of the head."""
    d, v = m["hidden_size"], m["vocab_size"]
    full, sliding = attention_layer_counts(m)
    attention = (full * attention_params(m, False)
                 + sliding * attention_params(m, True))
    sparse = _sparse_layers(m)
    ffn = (m["first_k_dense_replace"] * dense_mlp_params(m) + sparse * (
        m["n_routed_experts"] * expert_params(m) + shared_expert_params(m)
        + router_params(m)))
    norms = 2 * d * len(m["layer_types"]) + d
    head = 0 if m["tie_word_embeddings"] else d * v
    return attention + ffn + norms + d * v + head


def _row_width(m: dict, sliding: bool) -> int:
    """The numbers of a token's latent row: its KV latent and the one
    rotary key."""
    _, _, r, _, dr, _, _ = _shape(m, sliding)
    return r + dr


def cache_bytes_per_token(m: dict) -> int:
    """What one token keeps over the layers, bf16: a full layer its
    latent row and its index key, a sliding layer its wider row."""
    full, sliding = attention_layer_counts(m)
    return 2 * (full * (_row_width(m, False) + m["index_head_dim"])
                + sliding * _row_width(m, True))


def experts_touched_share(m: dict, live_tokens: float) -> float:
    """The share of the HELD experts that ``live_tokens`` tokens reach
    when each picks ``num_experts_per_tok`` of all the router's experts
    uniformly: 1 - (1 - k / E) ** n."""
    k, e = m["num_experts_per_tok"], _share(m)[0]
    return 1.0 - (1.0 - k / e) ** live_tokens


def _live_slots(counters: dict) -> float:
    samples = counters.get("occupancy_samples") or [0]
    return sum(samples) / len(samples)


def _rows_read(m: dict, counters: dict) -> tuple:
    """Of a decode step, over the live slots: (latent rows a full layer
    reads after its selection, index keys it scores, rows a sliding layer
    reads). From the mean of the live contexts' sum and the mean number
    of live slots: exact where every live context is on one side of
    ``index_topk`` and of the window, else an upper bound no greater
    than either limit's."""
    live, slots = counters.get("live_kv_tokens_mean", 0.0), _live_slots(
        counters)
    return (min(live, m["index_topk"] * slots), live,
            min(live, m["sliding_window_size"] * slots))


def attention_cache_bytes(m: dict, counters: dict) -> float:
    """Bytes of cache rows one decode step must read, bf16."""
    full, sliding = attention_layer_counts(m)
    selected, keys, windowed = _rows_read(m, counters)
    return 2.0 * (full * (selected * _row_width(m, False)
                          + keys * m["index_head_dim"])
                  + sliding * windowed * _row_width(m, True))


def decode_step_bytes(m: dict, counters: dict) -> float:
    """HBM bytes one decode step must move: the attention, dense,
    shared-expert and head weights (bf16) and the routers (float32)
    once; of the held experts' weights the share that the live tokens
    reach (at the mean number of live slots: ``occupancy_samples``); the
    cache rows of ``attention_cache_bytes`` once. The engine reads every
    held expert whatever the routing, so against this count its share of
    the roofline reads low, never high."""
    full, sliding = attention_layer_counts(m)
    sparse, dense = _sparse_layers(m), m["first_k_dense_replace"]
    attention = (full * attention_params(m, False)
                 + sliding * attention_params(m, True))
    always = (2.0 * (attention + dense * dense_mlp_params(m)
                     + sparse * shared_expert_params(m)
                     + m["hidden_size"] * m["vocab_size"])
              + 4.0 * sparse * router_params(m))
    experts = (2.0 * sparse * m["n_routed_experts"] * expert_params(m)
               * experts_touched_share(m, _live_slots(counters)))
    return always + experts + attention_cache_bytes(m, counters)


def latent_attention_cost(m: dict, counters: dict) -> dict:
    """``{"flops", "bytes"}`` of one decode step's attention and indexer
    (what ``latent_attn_op`` finds the time of), over the live slots. In
    the absorbed form a query scores a row against the latent itself and
    reads its value from the same row: ``heads x (r + dr + r) x 2``
    operations a row read, and the expansion's two halves once a slot
    (``heads x r x (dn + dv) x 2``); the indexer ``index_n_heads x
    (index_head_dim x 2 + 3)`` a key scored. Bytes: the rows and index
    keys read, bf16, and each layer's expansion once. At 128 heads over a
    576-wide row that is 241 operations a byte, the chip's ridge, so the
    floor is the larger of the two times (``flops.roofline_share``)."""
    slots = _live_slots(counters)
    selected, keys, windowed = _rows_read(m, counters)
    full, sliding = attention_layer_counts(m)
    flops = nbytes = 0.0
    for is_sliding, layers, rows in ((False, full, selected),
                                     (True, sliding, windowed)):
        heads, _, r, dn, dr, dv, _ = _shape(m, is_sliding)
        flops += layers * (rows * heads * (r + dr + r) * 2
                           + slots * heads * r * (dn + dv) * 2)
        nbytes += layers * 2.0 * (rows * (r + dr) + r * heads * (dn + dv))
    hi, di = m["index_n_heads"], m["index_head_dim"]
    flops += full * keys * hi * (di * 2 + 3)
    nbytes += full * 2.0 * keys * di
    return {"flops": flops, "bytes": nbytes}


def train_flops_per_token(m: dict, seq: int):
    """No training path for this family (16 bytes a parameter fit no cut
    of it within the floors; a share of the experts trains only with the
    exchange this cut leaves out)."""
    return None


def flash_train_cost(m: dict, batch: int, seq: int):
    return None


def grouped_expert_cost(m: dict, n_out: int, pairs: float,
                        here_share=None):
    """What one call of the grouped expert kernel must do, at this
    family's widths (gate and up): ``families.grouped_expert_call_cost``.
    For ``grouped_expert_ffn_roofline``."""
    from benchmark.families import grouped_expert_call_cost

    return grouped_expert_call_cost(
        hidden=m["hidden_size"], width=m["moe_intermediate_size"], held=m["n_routed_experts"],
        total=_share(m)[0], up_stacks=2, n_out=n_out, pairs=pairs,
        here_share=here_share)


def expert_ffn_op(m: dict):
    """A predicate on a device operation's HLO text: true for the ROUTED
    feed-forward's operations (router and held experts), told from the
    rest of a program by the expert axis in a shape they read or write.
    For ``expert_ffn_share.*``."""
    e, total = m["n_routed_experts"], _share(m)[0]
    d, f = m["hidden_size"], m["moe_intermediate_size"]
    shapes = re.compile(
        r"\[(?:\d+,)*(?:"
        rf"{e},{d},{f}|{e},{f},{d}"        # the held experts' weights
        rf"|{d},{total}"                   # the router
        rf"|\d+,{e},{f}|{e},\d+,{f}"       # [T, H, F], [H, T, F]
        r")\]")
    return lambda text: ("ragged-dot" in text
                         or shapes.search(text) is not None)


_SHAPE = re.compile(r"[a-z]+[0-9]*\[([0-9,]+)\]")
_LANES = 128


def _lanes(width: int) -> int:
    return -(-width // _LANES) * _LANES


def latent_attn_op(m: dict):
    """Predicates on a device operation's HLO text, by the shapes the
    attention and its indexer alone have in a decode program of this
    configuration (``system``: its slots, its pages): ``index`` for the
    indexer's operations (whatever reads or writes the index keys' pool
    or the pages gathered from it; a slot's gathered keys [slots, keys,
    index_head_dim] and its scores, their sort and the chosen positions,
    [slots, keys] and [slots, index_topk], keys being more than
    ``index_topk`` and whole pages); ``attention`` for those and the
    attention's own (the latent rows' pools and what is gathered from
    them, rows in whole lanes; the absorbed queries and outputs [slots,
    heads, r] and [slots, heads, lanes]; the scores [slots, heads, rows]
    over ``index_topk`` rows or a window's pages; the expansions [r,
    heads, dn + dv]). The projections before and after (``w_in``,
    ``wq_b``, ``wi_q``, ``wo``) are matmuls like a feed-forward's and
    count as neither. For ``latent_attn_share.*``,
    ``index_select_share.*`` and ``latent_attn_roofline.*``."""
    system = m["system"]
    slots, pages, page = (system["max_batch"], system["num_pages"],
                          system["page_size"])
    hi, di, topk = m["index_n_heads"], m["index_head_dim"], m["index_topk"]
    vocab = m["vocab_size"]
    shapes = [_shape(m, sliding) for sliding in (False, True)]
    lanes = {_lanes(r + dr) for _, _, r, _, dr, _, _ in shapes}
    heads_rank = {(h, w) for h, _, r, _, dr, _, _ in shapes
                  for w in (r, _lanes(r + dr))}
    expansions = {(r, h, e) for h, _, r, dn, _, dv, _ in shapes
                  for e in (dn + dv, dn, dv)}
    window_rows = -(-(page + m["sliding_window_size"] - 1) // page) * page
    heads = {h for h, *_ in shapes}

    def dims_of(text: str):
        for found in _SHAPE.finditer(text):
            yield tuple(int(n) for n in found.group(1).split(","))

    max_pages = -(-system["max_len"] // page)

    def many_keys(n: int) -> bool:
        """A decode table's keys: whole pages, a power of two of them or
        the table's full width, and more than the selection keeps."""
        table = n // page
        return (n % page == 0 and topk < n and table <= max_pages
                and (table & (table - 1) == 0 or table == max_pages))

    def index_shape(d: tuple) -> bool:
        if len(d) == 4:
            return d[1:] == (pages, page, di)
        if len(d) == 3:
            return d[1:] == (page, di) or (d[0] == slots and (
                (many_keys(d[1]) and d[2] in (di, hi))
                or (d[1] == hi and many_keys(d[2]))
                or (d[1] == topk and d[2] <= max_pages)))
        if len(d) == 2:
            return d[0] == slots and (many_keys(d[1]) or d[1] == topk)
        return d == (slots * topk,)

    def rows_shape(d: tuple) -> bool:
        """The latent rows' pools and what is gathered from them."""
        if len(d) == 4:
            return d[1:3] == (pages, page) and d[3] in lanes
        if len(d) == 3:
            return d[2] in lanes and (d[1] == page or d[0] == slots)
        return len(d) == 2 and d[0] == slots * topk and d[1] in lanes

    def attention_shape(d: tuple) -> bool:
        return rows_shape(d) or (len(d) == 3 and (d in expansions or (
            d[0] == slots and ((d[1], d[2]) in heads_rank or (
                d[1] in heads and d[2] in (topk, window_rows))))))

    def is_index(text: str) -> bool:
        dims = list(dims_of(text))
        return (any(index_shape(d) for d in dims)
                and not any(rows_shape(d) for d in dims))

    def is_attention(text: str) -> bool:
        return any(index_shape(d) or attention_shape(d)
                   for d in dims_of(text))

    return {"index": is_index, "attention": is_attention}
