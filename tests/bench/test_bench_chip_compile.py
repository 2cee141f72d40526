"""Compile the cells' device programs at their real shapes for a v5e that
is described, not attached: the d4 train step at batch 6 and the d10
fsdp-4 step at batch 16 (the largest the compiler accepts: the next size
up is refused for HBM), and the d12 engine's largest warmed prefill
programs and its decode program. What the chip's compiler refuses here it
refuses on the chip, at no chip time. Nothing runs: nothing here is a
result or a time. One file, the topology in a fixture (several workers
import this module; only the one given it may load the TPU's library)."""

import json
import math
import os
from functools import partial

os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else it logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmark import systems

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HBM = 15.75e9       # what the compiler gives a v5e chip's programs


def config(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def v5e_2x2():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler, or it is taken
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: the next run would warn
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def compile_step(cfg, devices):
    from ray_tpu.parallel.mesh import create_mesh
    from ray_tpu.train.trainer import JaxTrainer, TrainConfig

    s = cfg["system"]
    trainer = JaxTrainer(
        systems.model_config(cfg), TrainConfig(
            mesh_axes=dict(s["mesh_axes"]), strategy=s["strategy"],
            fused_loss=s["fused_loss"], warmup_steps=s["warmup_steps"]),
        mesh=create_mesh(dict(s["mesh_axes"]), devices=devices))
    trainer.attn_impl = "flash"     # "auto" sees the CPU under test
    state = jax.tree.map(
        lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
        trainer.abstract_state(), trainer.state_shardings())
    tokens = jax.ShapeDtypeStruct((s["batch_sequences"], 2048 + 1), jnp.int32)
    tokens = jax.ShapeDtypeStruct(
        tokens.shape, tokens.dtype,
        sharding=trainer._batch_shardings(tokens))
    return jax.jit(trainer._step, donate_argnums=(0,)).lower(
        state, tokens).compile()


def test_d4_train_step_fits_one_chip(v5e_2x2):
    compiled = compile_step(config("mistral-7b-v0.3-d4"), v5e_2x2[:1])
    assert compiled.as_text().count("tpu_custom_call") == 3
    m = compiled.memory_analysis()
    assert m.temp_size_in_bytes < HBM


def test_d10_fsdp4_train_step_fits_four_chips(v5e_2x2):
    compiled = compile_step(config("mistral-7b-v0.3-d10"), v5e_2x2)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 3 and "all-gather" in text
    assert compiled.memory_analysis().temp_size_in_bytes < HBM


@pytest.fixture(scope="module")
def d12_shapes(v5e_2x2):
    from ray_tpu.models import llama

    cfg = config("mistral-7b-v0.3-d12")
    one = SingleDeviceSharding(v5e_2x2[0])
    model = systems.model_config(cfg)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    params = jax.tree.map(
        lambda a: shape(a.shape, a.dtype),
        jax.eval_shape(partial(llama.init_params, model), jax.random.key(0)))
    s = cfg["system"]
    pool = shape((model.n_layers, s["num_pages"], s["page_size"],
                  model.n_kv_heads, model.head_dim), jnp.bfloat16)
    scale = shape((model.n_layers, 1, 1, 1), jnp.float32)
    key = jax.eval_shape(lambda: jax.random.key(0))
    return model, s, params, (pool, pool, scale, scale), key, shape


def _impl(engine, name):
    if not hasattr(engine, name):
        pytest.skip(f"the engine has no {name} now (see _lower)")
    return getattr(engine, name)


def _lower(fn, *args):
    """These two tests reach the engine's programs by their signatures,
    which the benchmark itself does not (it submits requests). A later PR
    that changes a signature cannot edit this file: the test then skips,
    and the cell's own first run on the chip is what compiles the shapes."""
    try:
        return fn.lower(*args)
    except TypeError as e:
        pytest.skip(f"the engine's program takes other arguments now: {e}")


# group x new tokens x window pages: the largest cells the traffic files'
# ``prefill_limits`` admit (``max_score_elements`` 8388608), and one past
# them that compiles since the pool is carried in place. The limit is the
# traffic files', which this test mirrors and does not set: raising it to
# what now fits is the serve cells' refit (PERF.md section 7).
WITHIN_LIMITS = [(2, 2048, 16), (4, 1024, 16), (4, 1024, 8), (1, 2048, 16)]
PAST_LIMITS = [(4, 2048, 16)]


@pytest.mark.parametrize("n,tokens,window_pages", WITHIN_LIMITS + PAST_LIMITS)
def test_d12_prefill_programs_fit(d12_shapes, n, tokens, window_pages):
    """The largest prefill programs the cells warm, and the program for
    four 2048-token prompts, which was refused while the layer scan kept a
    second page pool (PERF.md, PR 23 fault 1): each fits the chip beside
    nothing else and holds the pool once, as the donated argument its
    result aliases; what it needs besides is less than a pool."""
    from ray_tpu.serve.paged_llm import PagedLLMEngine

    model, s, params, pools, key, shape = d12_shapes
    within = (n, tokens, window_pages) in WITHIN_LIMITS
    assert within == (n * tokens * window_pages * s["page_size"] <= 8388608)
    fn = jax.jit(partial(_impl(PagedLLMEngine, "_paged_prefill_impl"), model,
                         page_size=s["page_size"], quantized=False),
                 donate_argnums=(1, 2, 3, 4))
    compiled = _lower(
        fn, params, *pools, shape((n, window_pages), jnp.int32),
        shape((n, tokens), jnp.int32), shape((n,), jnp.int32),
        shape((n,), jnp.int32), shape((n,), jnp.float32), key).compile()
    m = compiled.memory_analysis()
    pool_bytes = 2 * math.prod(pools[0].shape) * pools[0].dtype.itemsize
    assert pool_bytes == pytest.approx(3.42e9, rel=0.01)
    assert systems.program_bytes(compiled) < HBM
    assert m.alias_size_in_bytes >= pool_bytes      # in place, not beside
    # 2.2 GB at 2 x 2048 x 16, the largest within the limits; 4.36 GB of
    # scores and activations at 4 x 2048 x 16
    assert m.temp_size_in_bytes < (2.4e9 if within else 4.6e9)


@pytest.mark.parametrize("chunk,page_bucket", [(16, 16), (8, 8)])
def test_d12_decode_programs_fit(d12_shapes, chunk, page_bucket):
    from ray_tpu.serve.paged_llm import PagedLLMEngine

    model, s, params, pools, key, shape = d12_shapes
    b = s["max_batch"]
    fn = jax.jit(partial(_impl(PagedLLMEngine, "_paged_decode_impl"), model,
                         chunk=chunk, page_size=s["page_size"],
                         quantized=False), donate_argnums=(1, 2, 3, 4))
    compiled = _lower(
        fn, params, *pools, shape((b, page_bucket), jnp.int32),
        shape((b,), jnp.int32), shape((b,), jnp.int32),
        shape((b,), jnp.bool_), shape((b,), jnp.float32), key).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2e9
