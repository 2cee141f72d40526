"""Compile ``serve-code-gen``'s largest device programs at their real
shapes for a v5e that is described, not attached (as
``test_bench_chip_compile.py`` does for the cells before it): the decode
program over the 32-page table at 64 slots, the warm-up's cold prompt of
4,095 tokens and the two-prompt 2,048-token prefill. Each has to fit the
chip BESIDE the live arrays it does not itself hold, and to keep the page
pool in place. What the chip's compiler refuses here it refuses on the
chip, at no chip time. Nothing runs: nothing here is a result or a time.
One file, the topology in a fixture (several workers import this module;
only the one given it may load the TPU's library)."""

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
KERNEL = "paged_decode_attn"


@pytest.fixture(scope="module")
def v5e():
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
    yield topo.devices[0]
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def cell(v5e):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "laguna-s-2.1-ep4-d5.json")) as f:
        cfg = json.load(f)
    one = SingleDeviceSharding(v5e)
    model = systems.model_config(cfg)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    params = jax.tree.map(
        lambda a: shape(a.shape, a.dtype),
        jax.eval_shape(partial(systems.family(cfg).init_params, model),
                       jax.random.key(0)))
    s = cfg["system"]
    pool = shape((model.n_layers, s["num_pages"], s["page_size"],
                  model.n_kv_heads, model.head_dim), jnp.bfloat16)
    scale = shape((model.n_layers, 1, 1, 1), jnp.float32)
    key = jax.eval_shape(lambda: jax.random.key(0))
    return model, s, params, (pool, pool, scale, scale), key, shape


def _lower(name, *args, **static):
    """The engine's program by its signature, which the benchmark itself
    does not use (it submits requests): where a later PR changes it, skip,
    and the cell's own first run on the chip compiles the shapes."""
    from ray_tpu.serve.paged_llm import PagedLLMEngine

    if not hasattr(PagedLLMEngine, name):
        pytest.skip(f"the engine has no {name} now")
    model, *rest = args
    fn = jax.jit(partial(getattr(PagedLLMEngine, name), model, **static),
                 donate_argnums=(1, 2, 3, 4))
    try:
        return fn.lower(*rest)
    except TypeError as e:
        pytest.skip(f"the engine's program takes other arguments now: {e}")


def _sizes(cell):
    _, _, params, pools, _, _ = cell
    weights = sum(math.prod(a.shape) * a.dtype.itemsize
                  for a in jax.tree.leaves(params))
    pool = 2 * math.prod(pools[0].shape) * pools[0].dtype.itemsize
    return weights, pool


def test_the_live_arrays_are_a_deployments(cell):
    weights, pool = _sizes(cell)
    assert weights == pytest.approx(6.01e9, rel=0.005)
    assert pool == pytest.approx(6.04e9, rel=0.005)      # 2,304 pages
    assert weights + pool > 11e9     # the issue's floor (the driver's: 4 GB)


@pytest.mark.parametrize("n,tokens,window_pages", [
    (1, 4096, 32), (2, 2048, 16)], ids=["cold-4095", "two-cold-2048"])
def test_prefill_programs_fit_beside_the_live_arrays(cell, n, tokens,
                                                     window_pages):
    """The warm-up's cold prompt of ``max_len - 1`` tokens, and two cold
    prompts of 2,048 handed over together (``max_waiting`` 2): full layers
    go over their queries in blocks there (3 and 1.5 GiB of scores
    otherwise), sliding layers window by window; the program holds the
    pool once, as the donated argument its result aliases."""
    model, s, params, pools, key, shape = cell
    compiled = _lower(
        "_paged_prefill_impl", model, params, *pools,
        shape((n, window_pages), jnp.int32), shape((n, tokens), jnp.int32),
        shape((n,), jnp.int32), shape((n,), jnp.int32),
        shape((n,), jnp.float32), key, page_size=s["page_size"],
        quantized=False).compile()
    m = compiled.memory_analysis()
    weights, pool = _sizes(cell)
    assert m.alias_size_in_bytes >= pool                 # in place
    assert systems.program_bytes(compiled) < HBM
    # beside the live arrays (the program's arguments ARE the live
    # arrays: weights and pools) its temporaries leave a GB and more
    assert m.temp_size_in_bytes < 1.6e9
    assert weights + pool + m.temp_size_in_bytes < HBM - 1.0e9


@pytest.mark.parametrize("chunk", [16, 8])
def test_decode_programs_fit_and_walk_the_pages_in_place(cell, chunk):
    model, s, params, pools, key, shape = cell
    b = s["max_batch"]
    compiled = _lower(
        "_paged_decode_impl", model, params, *pools, shape((b, 32), jnp.int32),
        shape((b,), jnp.int32), shape((b,), jnp.int32),
        shape((b,), jnp.bool_), shape((b,), jnp.float32), key, chunk=chunk,
        page_size=s["page_size"], quantized=False).compile()
    text, m = compiled.as_text(), compiled.memory_analysis()
    # one kernel instruction a run of the plan (three runs), none of them
    # gathering a window; no copy of a pool
    assert len(set(
        line.split("=")[0].strip() for line in text.splitlines()
        if f"%{KERNEL}" in line.split("=")[0]
        and "tpu_custom_call" in line)) == 3
    _, pool = _sizes(cell)
    assert m.alias_size_in_bytes >= pool
    assert m.temp_size_in_bytes < 0.3e9
