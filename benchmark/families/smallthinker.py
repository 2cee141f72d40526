"""Window-and-NoPE sparse-expert decoders served by
``ray_tpu.models.smallthinker`` (PowerInfer SmallThinker-21BA3B-Instruct,
``model_name`` ``smallthinker_21b_instruct``): the adapter from the
published keys to the program's config class, the plain reference of the
block, and its operation and byte counts (``benchmark/families/
__init__.py`` says what a family is).

The reference follows the published ``config.json`` and the catalog's
description of the family, layer by layer. ``h = rmsnorm(x)`` in float32;
q, k and v from ``h`` with 28 query heads over 4 KV heads of 128, no bias,
no QK-norm; rotary embedding (rotate-half over the whole head, ``rope_theta``)
where ``rope_layout[l]`` is 1 and NONE where it is 0 (of both per-layer
lists the first ``num_hidden_layers`` entries are the layers that are run);
causal softmax attention, under the window (keys ``i - sliding_window_size < j <= i``)
where ``sliding_window_layout[l]`` is 1; ``x = x + attn @ wo``. THE ROUTER
READS ``h``, the layer's normed input, the rows attention's projections
read, not the stream behind the attention ("router placed before
attention"): float32 logits ``h @ router``, the
``moe_num_active_primary_experts`` largest LOGITS, weights a softmax over
those alone (``moe_primary_router_apply_softmax``). The experts read ``g =
rmsnorm(x)`` of the stream behind the attention (the layer's second norm)
and are ReGLUs: ``x = x + sum_k w_k (relu(g @ gate_k) * (g @ up_k)) @
down_k``; no shared expert; nothing "secondary" is run (``config`` has only
``moe_num_primary_experts``, and ``config`` wins over the description).
Final RMSNorm; untied head.

What ``config.json`` has no key for is a NAMED DEPARTURE of ``logits``,
with the reading taken as its default, so that the other reading is one
argument away (the configuration file lists each under ``assumed``):

- ``router_input="layer_input"``: as above; ``"post_attention"``: the
  router reads ``g``, where every other expert model's router sits;
- ``gate_act="relu"`` ("sparse ReGLU"); ``"silu"``: a SwiGLU;
- ``rope="layout"``: by ``rope_layout``; ``"everywhere"``: the full layers
  rotate too;
- ``window="published"``; ``None``: a sliding layer attends over
  everything.

It reads the program's parameter layout, which is data, not code
(``params["blocks"]`` maps ``layers<first>[-<last>]`` to that run of
identical layers' weights stacked on a leading axis; ``wqkv`` holds the
columns q | k | v), and imports nothing from the program. Three departures
in FORM, none in value, all so that the check's 4,632 positions fit on the
chip beside the engine: the sum over a token's chosen experts is a loop
over all experts, each applied to every token and kept where it is among
the token's chosen (as ``families/olmoe.py`` does, and for its reason);
attention goes over the queries in blocks of ``_QUERY_BLOCK`` (all 28
heads' float32 scores of 4,632 x 4,632 would be 2.4 GB); the head is
applied a slice of the vocabulary at a time (its float32 copy alone would
be 1.6 GB beside the 2.8 GB of logits). On a TPU a float32 matrix
multiplication runs in lower precision unless told otherwise: ``logits``
runs under ``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp

_QUERY_BLOCK = 512


# -- the adapter: the one part that touches the program -----------------

def _layouts(config: dict) -> tuple:
    """(``sliding_window_layout``, ``rope_layout``) of the layers that are
    run: the first ``num_hidden_layers`` entries of the published lists (a
    file cut in depth keeps both lists whole)."""
    n = config["num_hidden_layers"]
    return (tuple(config["sliding_window_layout"][:n]),
            tuple(config["rope_layout"][:n]))


def model_config(config: dict):
    from ray_tpu.models import smallthinker

    sliding, rotates = _layouts(config)
    return smallthinker.SmallThinkerConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        sliding_window_layout=sliding, rope_layout=rotates,
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], window=config["sliding_window_size"],
        d_expert=config["moe_ffn_hidden_size"],
        n_experts=config["moe_num_primary_experts"],
        top_k=config["moe_num_active_primary_experts"],
        norm_topk_prob=config["norm_topk_prob"],
        router_softmax=config["moe_primary_router_apply_softmax"],
        rope_theta=float(config["rope_theta"]),
        rms_eps=float(config["rms_norm_eps"]),
        dtype={"bfloat16": "bfloat16", "float32": "float32"}[
            config.get("torch_dtype", "bfloat16")],
        tie_embeddings=config["tie_word_embeddings"])


def init_params(model_cfg, key):
    from ray_tpu.models import smallthinker

    return smallthinker.init_params(model_cfg, key)


# -- the plain reference -------------------------------------------------

def _rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rope(x, positions, theta):
    """x [b, s, h, hd]; rotate pairs (i, i + hd/2)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions[..., None].astype(jnp.float32) * inv        # [b, s, hd/2]
    sin, cos = jnp.sin(ang)[:, :, None, :], jnp.cos(ang)[:, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, window):
    """Causal softmax attention of q [b, s, h, hd] over k, v [b, s, kv, hd]
    (each KV head serves h / kv query heads), under ``window`` keys where
    it is given; the queries in blocks of ``_QUERY_BLOCK``, each over all
    the keys and masked by position."""
    b, s, heads, hd = q.shape
    rep = heads // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    blocks = -(-s // _QUERY_BLOCK)
    pad = blocks * _QUERY_BLOCK - s
    qb = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(
        b, blocks, _QUERY_BLOCK, heads, hd)
    j = jnp.arange(s)[None, :]

    def one_block(args):
        qi, first = args                                # [b, B, h, hd]
        i = first + jnp.arange(_QUERY_BLOCK)[:, None]
        seen = j <= i
        if window is not None:
            seen = seen & (j > i - window)
        att = jnp.einsum("bqhd,bkhd->bhqk", qi, k) * hd ** -0.5
        att = jnp.where(seen, att, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(att, -1), v)

    out = jax.lax.map(one_block, (jnp.moveaxis(qb, 1, 0),
                                  jnp.arange(blocks) * _QUERY_BLOCK))
    return jnp.moveaxis(out, 0, 1).reshape(b, -1, heads, hd)[:, :s]


def _routed_ffn(g, scored, p, top_k, gate_act):
    """g [b, s, d] float32 -> the routed experts' sum, float32, by the
    router's logits of ``scored`` [b, s, d]: the ``top_k`` largest logits,
    a softmax over those alone."""
    logits = scored @ p["router"].astype(jnp.float32)
    kth = jnp.sort(logits, axis=-1)[..., -top_k]
    chosen = logits >= kth[..., None]
    weight = jax.nn.softmax(jnp.where(chosen, logits, -jnp.inf), axis=-1)
    act = {"relu": jax.nn.relu, "silu": jax.nn.silu}[gate_act]

    def one_expert(y, expert):
        gate, up, down, w = expert
        gate, up, down = (a.astype(jnp.float32) for a in (gate, up, down))
        out = (act(g @ gate) * (g @ up)) @ down
        return y + jnp.where(w[..., None] > 0.0, w[..., None] * out, 0.0), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(g),
                        (p["wi_gate"], p["wi_up"], p["wo_e"],
                         jnp.moveaxis(weight, -1, 0)))
    return y


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "head_dim", "eps", "window", "theta", "top_k",
    "router_input", "gate_act"))
def _layer(x, p, *, heads, kv_heads, head_dim, eps, window, theta, top_k,
           router_input, gate_act):
    """One decoder layer on x [b, s, d] float32; p holds this layer's
    weights in their stored dtype. ``window``: None, or the keys a query
    sees. ``theta``: None (no rotary embedding), or the rotary base."""
    f32 = lambda name: p[name].astype(jnp.float32)   # noqa: E731
    b, s, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    h = _rms_norm(x, f32("attn_norm"), eps)
    qdim, kvdim = heads * head_dim, kv_heads * head_dim
    qkv = h @ f32("wqkv")
    q = qkv[..., :qdim].reshape(b, s, heads, head_dim)
    k = qkv[..., qdim:qdim + kvdim].reshape(b, s, kv_heads, head_dim)
    v = qkv[..., qdim + kvdim:].reshape(b, s, kv_heads, head_dim)
    if theta is not None:
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    out = _attention(q, k, v, window)
    x = x + out.reshape(b, s, qdim) @ f32("wo")
    g = _rms_norm(x, f32("mlp_norm"), eps)
    scored = {"layer_input": h, "post_attention": g}[router_input]
    return x + _routed_ffn(g, scored, p, top_k, gate_act)


@functools.partial(jax.jit, static_argnames=("eps",))
def _final_norm(x, final_norm, *, eps):
    return _rms_norm(x, final_norm.astype(jnp.float32), eps)


@functools.cache
def _head_slice():
    """``out`` [b, s, vocab] with the logits of ``width`` ids from
    ``first`` on written into their place. ``out`` is DONATED (off the CPU,
    which has no donation), so that the slices land in the one array: a
    loop inside one program is given the result twice, as its output and
    as its loop's value, 5.6 GB where the chip has 3."""
    def write(out, x, lm_head, first, *, width):
        w = jax.lax.dynamic_slice_in_dim(lm_head, first, width, axis=1)
        return jax.lax.dynamic_update_slice_in_dim(
            out, x @ w.astype(jnp.float32), first, axis=2)

    donate = () if jax.default_backend() == "cpu" else (0,)
    return jax.jit(write, static_argnames=("width",), donate_argnums=donate)


def _head(x, final_norm, lm_head, *, eps):
    """Float32 logits of x [b, s, d], a slice of the vocabulary at a time,
    each written into its place of the one result."""
    x = _final_norm(x, final_norm, eps=eps)
    vocab = lm_head.shape[1]
    slices = next(n for n in (16, 8, 4, 2, 1) if vocab % n == 0)
    out = jnp.zeros((*x.shape[:2], vocab), jnp.float32)
    for i in range(slices):
        out = _head_slice()(out, x, lm_head, i * (vocab // slices),
                            width=vocab // slices)
    return out


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


def logits(config: dict, params: dict, tokens, *, router_input="layer_input",
           gate_act="relu", rope="layout", window="published") -> jax.Array:
    """Float32 logits [b, s, vocab] of ``tokens`` [b, s], one layer at a
    time. The keyword arguments are the named departures of the module
    docstring; their defaults are the configuration's reading."""
    if window == "published":
        window = config["sliding_window_size"]
    if rope not in ("layout", "everywhere"):
        raise ValueError(
            f"rope must be 'layout' or 'everywhere', not {rope!r}")
    theta = float(config["rope_theta"])
    kw = dict(heads=config["num_attention_heads"],
              kv_heads=config["num_key_value_heads"],
              head_dim=config["head_dim"], eps=float(config["rms_norm_eps"]),
              top_k=config["moe_num_active_primary_experts"],
              router_input=router_input, gate_act=gate_act)
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embedding"], tokens)
        for sliding, rotates, p in zip(
                *_layouts(config), _layers(params["blocks"]), strict=True):
            x = _layer(x, p, window=window if sliding else None,
                       theta=theta if rotates or rope == "everywhere"
                       else None, **kw)
        head = (params["embedding"].T if config["tie_word_embeddings"]
                else params["lm_head"])
        return _head(x, params["final_norm"], head, eps=kw["eps"])


# -- the counts ----------------------------------------------------------

def attention_layer_counts(m: dict) -> tuple:
    """(full layers, sliding layers)."""
    sliding = sum(_layouts(m)[0])
    return m["num_hidden_layers"] - sliding, sliding


def attention_params(m: dict) -> int:
    """A layer's attention weights: q | k | v and ``wo`` (the norm's
    vector left out)."""
    d, hd = m["hidden_size"], m["head_dim"]
    q, kv = m["num_attention_heads"] * hd, m["num_key_value_heads"] * hd
    return d * (q + 2 * kv) + q * d


def expert_params(m: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * m["hidden_size"] * m["moe_ffn_hidden_size"]


def router_params(m: dict) -> int:
    return m["hidden_size"] * m["moe_num_primary_experts"]


def layer_params(m: dict) -> int:
    """Attention, the router, the two norms and every expert."""
    return (attention_params(m) + router_params(m) + 2 * m["hidden_size"]
            + m["moe_num_primary_experts"] * expert_params(m))


def total_params(m: dict) -> int:
    d, v = m["hidden_size"], m["vocab_size"]
    head = 0 if m["tie_word_embeddings"] else d * v
    return m["num_hidden_layers"] * layer_params(m) + d * v + head + d


def kv_bytes_per_token_layer(m: dict) -> int:
    """Keys and values of one token in one layer, bf16."""
    return 2 * 2 * m["num_key_value_heads"] * m["head_dim"]


def _live_slots(counters: dict) -> float:
    samples = counters.get("occupancy_samples") or [0]
    return sum(samples) / len(samples)


def experts_touched(m: dict, counters: dict) -> float:
    """How many of a layer's experts one decode step's rows reach: the
    program's own count where the run's counters hold it
    (``experts_touched_mean``), else what the live slots' tokens reach
    when each picks its experts uniformly: E x (1 - (1 - k / E) ** n)."""
    counted = counters.get("experts_touched_mean")
    if counted is not None:
        return float(counted)
    k, e = m["moe_num_active_primary_experts"], m["moe_num_primary_experts"]
    return e * (1.0 - (1.0 - k / e) ** _live_slots(counters))


def attention_kv_bytes(m: dict, counters: dict) -> float:
    """Bytes of keys and values one decode step must read: in a full
    layer every live token's (the counter ``live_kv_tokens_mean``), in a
    sliding layer ``sliding_window_size`` tokens' for each live slot, or
    all of them where that is fewer. (Exact where every live context is
    past the window, as in ``serve-brief-gen``, or none is.)"""
    full, sliding = attention_layer_counts(m)
    live = counters.get("live_kv_tokens_mean", 0.0)
    seen = min(live, m["sliding_window_size"] * _live_slots(counters))
    return kv_bytes_per_token_layer(m) * (full * live + sliding * seen)


def decode_step_bytes(m: dict, counters: dict) -> float:
    """HBM bytes one decode step must move: the attention and head weights
    (bf16) and the routers (float32) once; the weights of the experts the
    step's rows reach (``experts_touched``); the keys and values of
    ``attention_kv_bytes`` once. The engine reads every expert whatever
    the routing, so against this count its share of the roofline reads
    low, never high."""
    layers = m["num_hidden_layers"]
    always = (2.0 * (layers * attention_params(m)
                     + m["hidden_size"] * m["vocab_size"])
              + 4.0 * layers * router_params(m))
    experts = 2.0 * layers * expert_params(m) * experts_touched(m, counters)
    return always + experts + attention_kv_bytes(m, counters)


def train_flops_per_token(m: dict, seq: int):
    """No training path for this family (the trainer runs one dense block
    repeated)."""
    return None


def flash_train_cost(m: dict, batch: int, seq: int):
    return None


def grouped_expert_cost(m: dict, n_out: int, pairs: float,
                        here_share=None):
    """What one call of the grouped expert kernel must do, at this
    family's widths (gate and up, then down: three stacks of [2560, 768]):
    ``families.grouped_expert_call_cost``. For
    ``grouped_expert_ffn_roofline``."""
    from benchmark.families import grouped_expert_call_cost

    experts = m["moe_num_primary_experts"]
    return grouped_expert_call_cost(
        hidden=m["hidden_size"], width=m["moe_ffn_hidden_size"],
        held=experts, total=experts, up_stacks=2, n_out=n_out, pairs=pairs,
        here_share=here_share)


def expert_ffn_op(m: dict):
    """A predicate on a device operation's HLO text: true for the routed
    feed-forward's operations (router and experts), told from the rest of
    a program by the expert axis in a shape they read or write, or by the
    grouped kernel's name. For ``expert_ffn_share.*`` and
    ``prefill_expert_share.*``."""
    e, d, f = (m["moe_num_primary_experts"], m["hidden_size"],
               m["moe_ffn_hidden_size"])
    shapes = re.compile(
        r"\[(?:\d+,)*(?:"
        rf"{e},{d},{f}|{e},{f},{d}"        # the experts' weights
        rf"|{d},{e}"                       # the router
        rf"|\d+,{e},{f}|{e},\d+,{f}"       # [T, E, F], [E, T, F]
        r")\]")
    return lambda text: ("grouped_expert_ffn" in text
                         or shapes.search(text) is not None)
