"""The fused loss with the vocabulary split over the mesh
(``llama._vocab_split_cross_entropy``, the layout
``parallel.sharding.loss_layout`` reads from the mesh and the rules) against
the dense loss on one device: the value and EVERY gradient leaf. The
benchmark's ``correct`` sees two steps' losses and no gradient, so a wrong
``d head`` or a hidden cotangent summed in bf16 is caught here."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.parallel.mesh import create_mesh
from ray_tpu.parallel.sharding import PRESETS, loss_layout
from ray_tpu.train.trainer import JaxTrainer, TrainConfig
from ray_tpu.util import tracing

Z_LOSS = 1e-3


def _cfg(vocab, dtype="float32"):
    return llama.LlamaConfig(vocab_size=vocab, d_model=16, n_layers=1,
                             n_heads=2, n_kv_heads=2, d_ff=32, head_dim=8,
                             remat="none", dtype=dtype)


def _trainer(cfg, axes, strategy):
    return JaxTrainer(
        cfg, TrainConfig(mesh_axes=axes, strategy=strategy, fused_loss=True,
                         loss_chunk=16),
        mesh=create_mesh(
            axes, devices=jax.devices()[:math.prod(axes.values())]))


def _batch(vocab, seed=0):
    """[4, 23] tokens: 88 rows, so the last chunk of 16 is ragged on one
    device and on every mesh below; the last three targets a row are -1."""
    batch = np.random.default_rng(seed).integers(
        0, vocab, size=(4, 23)).astype(np.int32)
    batch[:, -3:] = -1
    return batch


def _dense(cfg, params, batch):
    inputs, targets = batch[:, :-1], batch[:, 1:]
    logits = llama.forward(cfg, params, inputs, attn_impl="reference")
    return llama.cross_entropy_loss(
        logits, jnp.maximum(targets, 0), z_loss=Z_LOSS,
        mask=(targets != -1).astype(jnp.float32))


def _fused(trainer, params, batch):
    """``JaxTrainer._loss_fn``'s fused branch, with a z-loss and the mask
    left to the -1 convention."""
    hidden = llama.forward_hidden(
        trainer.model_cfg, params, batch[:, :-1],
        attn_impl=trainer.attn_impl, mesh=trainer.mesh,
        sp_axis=trainer.sp_axis)
    return llama.fused_cross_entropy(
        trainer.model_cfg, params, hidden, batch[:, 1:],
        chunk=trainer.cfg.loss_chunk, z_loss=Z_LOSS, mesh=trainer.mesh,
        rows=trainer.loss_rows, vocab_axes=trainer.loss_vocab_axes)


# mesh, preset, and the axes the vocabulary is split over where they divide it
_MESHES = [
    ({"fsdp": 4}, "fsdp", ("fsdp",)),
    ({"dp": 2, "fsdp": 2}, "fsdp", ("dp", "fsdp")),
    ({"fsdp": 2, "tp": 2}, "fsdp_tp", ("tp", "fsdp")),
    ({"fsdp": 1}, "fsdp", ()),
    # the other presets a user can pick with fused_loss=True
    ({"dp": 4}, "dp", ("dp",)),
    ({"tp": 2}, "tp", ()),
    ({"fsdp": 2, "sp": 2}, "fsdp_tp_sp", ("fsdp", "sp")),
    ({"dp": 2, "fsdp": 2, "tp": 2}, "fsdp_tp", ("tp", "dp", "fsdp")),
]


@pytest.mark.parametrize("vocab", [96, 98], ids=["divides", "does-not"])
@pytest.mark.parametrize(
    "axes,strategy,split", _MESHES,
    ids=["-".join(f"{k}{v}" for k, v in m[0].items()) for m in _MESHES])
def test_vocab_split_loss_and_every_gradient_match_dense(
        axes, strategy, split, vocab):
    """Float32 parameters, a ragged last chunk, -1 padding, z_loss > 0:
    the loss and the gradient of every leaf (the head's, the
    embedding's, the block's) are the dense one-device loss's, at the
    tolerance ``test_fused_cross_entropy_matches_dense`` holds. 98 is
    divided by tp's 2 and by no mesh's whole size: the loss then takes the
    plain path and says so."""
    cfg = _cfg(vocab)
    trainer = _trainer(cfg, axes, strategy)
    n_split = math.prod(axes[a] for a in split)
    assert trainer.loss_vocab_axes == (split if vocab % n_split == 0 else ())
    params = llama.init_params(cfg, jax.random.key(0))
    batch = _batch(vocab)
    want, want_grads = jax.value_and_grad(
        lambda p: _dense(cfg, p, batch))(params)
    got, got_grads = jax.jit(jax.value_and_grad(
        lambda p, b: _fused(trainer, p, b)))(
        jax.device_put(params, trainer.state_shardings().params),
        jax.device_put(batch, trainer._batch_shardings(batch)))
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    assert set(got_grads) == {"embedding", "blocks", "final_norm", "lm_head"}
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5),
        got_grads, want_grads)


def _steps_apart(a, b):
    """How many bf16 values lie between ``a`` and ``b``, element by
    element (0: the same bits)."""
    def line(x):        # sign-magnitude bits -> a monotonic integer
        bits = np.asarray(x).view(np.uint16).astype(np.int32)
        return np.where(bits & 0x8000, -(bits & 0x7FFF), bits & 0x7FFF)
    return np.abs(line(a) - line(b))


def test_hidden_cotangent_is_summed_in_fp32_and_rounded_once():
    """bf16 head and rows: the hidden cotangent over {"fsdp": 4} is the
    one-device cotangent bit for bit, or one rounding away where the fp32
    sums' last places differ. Four partials rounded to bf16 BEFORE the
    sum (what autodiff gives a gathered bf16 chunk) are not: the same
    comparison of that sum finds elements two and more steps apart."""
    cfg = _cfg(128, dtype="bfloat16")
    trainer = _trainer(cfg, {"fsdp": 4}, "fsdp")
    key_h, key_w = jax.random.split(jax.random.key(3))
    hidden = jax.random.normal(key_h, (4, 22, 16), jnp.bfloat16)
    head = jax.random.normal(key_w, (16, 128), jnp.bfloat16) * 2
    targets = _batch(128)[:, 1:]

    def cotangent(**layout):
        return jax.jit(jax.grad(lambda h: llama.fused_cross_entropy(
            cfg, {"lm_head": head}, h, targets, chunk=16, **layout)
            * 4096.0))(hidden)

    one_device = cotangent()
    split = cotangent(mesh=trainer.mesh, rows=trainer.loss_rows,
                      vocab_axes=trainer.loss_vocab_axes)
    assert split.dtype == jnp.bfloat16
    apart = _steps_apart(split, one_device)
    assert apart.max() <= 1 and (apart == 0).mean() > 0.95

    # the sum this forbids, made by hand from the same partials: each
    # quarter of the vocabulary's cotangent rounded, then added in bf16
    logits = jnp.einsum("bsd,dv->bsv", hidden, head,
                        preferred_element_type=jnp.float32)
    d_logits = jax.grad(lambda l: llama.cross_entropy_loss(
        l, jnp.maximum(targets, 0),
        mask=(targets >= 0).astype(jnp.float32)) * 4096.0)(logits)
    rounded_first = sum(
        jnp.einsum("bsv,dv->bsd", d_logits[..., q:q + 32],
                   head[:, q:q + 32].astype(jnp.float32)
                   ).astype(jnp.bfloat16)
        for q in range(0, 128, 32))
    assert _steps_apart(rounded_first, one_device).max() >= 2


@pytest.mark.parametrize("preset", sorted(PRESETS))
def test_one_device_keeps_the_plain_path(preset):
    """A mesh of one device splits nothing, whatever the rules."""
    mesh = create_mesh({"fsdp": 1}, devices=jax.devices()[:1])
    assert loss_layout(mesh, PRESETS[preset], 32768) == (((), ()), ())


def test_compile_step_states_the_layout_once():
    """``train.compile_step``: one span a built step, with the layout
    ``JaxTrainer.loss_vocab_axes`` states, while tracing is on alone."""
    cfg = _cfg(96)
    batch = _batch(96)
    trainer = _trainer(cfg, {"dp": 2, "fsdp": 2}, "fsdp")
    state = trainer.init_state(jax.random.key(0))
    trainer.compile_step(state, batch)           # tracing off: no span
    trainer._jit_step.clear()
    tracing.enable_tracing()
    try:
        before = len(tracing.recorded_spans("train.compile_step"))
        state, metrics = trainer.train_step(state, batch)
        trainer.train_step(state, batch)         # built already: no span
        spans = tracing.recorded_spans("train.compile_step")[before:]
    finally:
        tracing.disable_tracing()
    assert [s["attrs"] for s in spans] == [
        {"loss_vocab_axes": ["dp", "fsdp"], "loss_vocab_shards": 4}]
    assert np.isfinite(float(metrics["loss"]))
