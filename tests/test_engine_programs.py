"""The engine's device programs (serve/engine_programs.py): the order of
their arguments, stated once, against what the lowered programs do; and
the one-way arrow between the two engine modules."""

import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.serve.engine_programs import (_DECODE, _PREFILL,
                                           EnginePrograms)

SLOTS, PAGES, PAGE = 2, 8, 128

# (module, tiny config): K/V twins; latent rows in two formats; K/V twins
# beside a state in every layer; runs that are a mixer, experts or
# attention alone
_PLANS = {"llama": "llama_tiny", "dots3_note": "dots3_note_tiny",
          "falcon_h1": "falcon_h1_tiny", "nemotron_h": "nemotron_h_tiny"}


def _programs(family):
    import importlib

    model = importlib.import_module(f"ray_tpu.models.{family}")
    cfg = getattr(model, _PLANS[family])()
    return EnginePrograms(
        cfg, model.init_params(cfg, jax.random.key(0)), max_batch=SLOTS,
        num_pages=PAGES, page_size=PAGE, kv_dtype="bf16")


def _inputs(order, rows, tokens):
    """Host inputs of a program over ``rows`` rows (decode: the slots; its
    ``tokens`` are one a slot), by the names its order states."""
    i32 = jnp.int32
    shaped = {
        "table": jnp.zeros((rows, 2), i32),
        "table_rows": jnp.zeros((rows, 2), i32),
        "tokens": jnp.ones(tokens, i32),
        "lengths": jnp.full((rows,), 3, i32),
        "slens": jnp.full((rows,), 5, i32),
        "starts": jnp.zeros((rows,), i32),
        "active": jnp.ones((rows,), bool),
        "temps": jnp.zeros((rows,), jnp.float32),
        "key": jax.random.key(1),
        "slots": jnp.arange(rows, dtype=i32)}
    return {name: shaped[name] for name in order.inputs + order.beside_state}


@pytest.mark.parametrize("family", sorted(_PLANS))
def test_the_stated_order_is_the_order_the_programs_alias_by(family):
    """The donated positions derived from the one stated order are the
    arguments each lowered program aliases to a result, pools to the
    leading results and state to the trailing ones, and no other
    argument; and a call by name hands pools and state back in place.
    A drift between ``arguments``, ``taken``, ``returned`` and
    ``donated`` donates an input that aliases nothing, or leaves a store
    beside its copy."""
    programs = _programs(family)
    n_pools, n_state = len(programs.pools), len(programs.state)
    cases = [(_DECODE, programs._decode_paged(2, 2),
              _inputs(_DECODE, SLOTS, (SLOTS,))),
             (_PREFILL, programs._prefill_paged(2),
              _inputs(_PREFILL, 2, (2, 16)))]
    for order, program, inputs in cases:
        args = order.arguments(programs.params, programs.pools, inputs,
                               programs.state)
        lowered = program.lower(*args)
        # donated: exactly the pools and the state, where they are passed
        donated = order.donated(n_pools, n_state)
        assert [args[i] for i in donated] == [*programs.pools,
                                              *programs.state]
        flags = [[leaf.donated for leaf in jax.tree.leaves(info)]
                 for info in lowered.args_info[0]]
        assert [i for i, leaf in enumerate(flags) if any(leaf)] == list(
            donated) and all(all(flags[i]) for i in donated)
        # aliased: every donated argument, to the results the order says
        text = lowered.as_text()
        main = text[text.index("@main("):].split("\n", 1)[0]
        aliased = sorted(int(n) for n in re.findall(
            r"tf\.aliasing_output = (\d+)", main))
        results = n_pools + len(order.results)
        # (where each result's arrays lie among the flattened outputs: a
        # dense plan's ``stats`` is no array, a routed one's several)
        leaves = [len(jax.tree.leaves(out)) for out in lowered.out_info]
        flat = [sum(leaves[:i]) for i in range(len(leaves))]
        assert aliased == [flat[i] for i in (
            *range(n_pools), *range(results, results + n_state))]
        assert "jax.buffer_donor" not in main
    # by name: the stores come back in the buffers they went in at
    held = [a.unsafe_buffer_pointer() for a in
            (*programs.pools, *programs.state)]
    before = list(programs.pools) + list(programs.state)
    program, arguments = programs.prefill(2, **cases[1][2])
    firsts = programs.prefilled(program(*arguments))
    program, arguments = programs.decode(2, 2, **cases[0][2])
    out = programs.decoded(program(*arguments))
    jax.block_until_ready((firsts, out))
    assert set(out) == set(_DECODE.results) and firsts.shape == (2,)
    assert out["toks"].shape == (2, SLOTS)
    assert all(a.is_deleted() for a in before)
    assert [a.unsafe_buffer_pointer() for a in
            (*programs.pools, *programs.state)] == held
    assert [(a.shape, a.dtype) for a in before] == [
        (a.shape, a.dtype) for a in (*programs.pools, *programs.state)]


def test_the_programs_module_imports_nothing_of_the_loop():
    """``engine_programs`` is what the loop imports, never the other way:
    importing it brings in neither ``paged_llm`` nor what only the loop
    needs (its threads, its queue of spans)."""
    code = ("import sys; import ray_tpu.serve.engine_programs as m; "
            "assert 'ray_tpu.serve.paged_llm' not in sys.modules; "
            "names = set(vars(m)); "
            "assert not names & {'threading', 'queue', '_tracing'}, names")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120,
                   env={**os.environ, "JAX_PLATFORMS": "cpu"})
