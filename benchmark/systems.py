"""Where the benchmark touches the program: it builds the system under
test (the model config object, ``JaxTrainer``, ``PagedLLMEngine``) from a
configuration file, through the adapter of the family the file names, and
reads the engine's counters and what the compiler says the compiled
programs need. It calls the program through its public surface only: no
jitted partial by its signature, no bucketing rule, no private array.
Beside it only a family's two adapter functions build anything of the
program's, and ``program_spans.py`` reads the engine's recorded spans;
everything else under ``benchmark/`` is yardstick.

``family(config)`` is also the one way to a model's block: its plain
reference and its operation and byte counts live in the family's module
(``benchmark/families/__init__.py``), and the runners and readers reach
them through here, so that none of them names a family."""

from __future__ import annotations

import functools
import importlib

from benchmark import families


def family(config: dict):
    """The configuration's model family, found by the name under its
    ``family`` key and by nothing else: ``benchmark/families/<family>.py``,
    with the adapter, the plain reference and the counts (every name of
    ``families.API``; a module that lacks one is refused here, in a CPU
    rehearsal too, and not at the first reader that asks on the chip)."""
    try:
        module = importlib.import_module(
            "benchmark.families." + config["family"])
    except ModuleNotFoundError as e:
        raise SystemExit(f"benchmark: no model family "
                         f"{config['family']!r}: {e}") from e
    missing = [name for name in families.API if not hasattr(module, name)]
    if missing:
        raise SystemExit(f"benchmark: model family {config['family']!r} "
                         f"lacks {missing} (benchmark/families/__init__.py)")
    return module


def model_config(config: dict):
    """The program's config object for a configuration file (published
    key names at its top level, the program's settings under ``system``)."""
    return family(config).model_config(config)


def seed_key(seed: int):
    """A PRNG key from any whole number up to a little over 2**31."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed % (2 ** 31)),
                              seed // (2 ** 31))


def make_trainer(config: dict):
    from ray_tpu.train.trainer import JaxTrainer, TrainConfig

    s = config["system"]
    return JaxTrainer(model_config(config), TrainConfig(
        mesh_axes=dict(s["mesh_axes"]), strategy=s["strategy"],
        fused_loss=s["fused_loss"], warmup_steps=s["warmup_steps"]))


def make_params(config: dict, seed: int):
    """Weights made on the device in one jitted call from the seed, in the
    type they are served in."""
    import jax

    return jax.block_until_ready(jax.jit(functools.partial(
        family(config).init_params, model_config(config)))(seed_key(seed)))


def make_engine(config: dict, params):
    """The paged engine on weights handed to it as an argument."""
    from ray_tpu.serve.paged_llm import PagedLLMEngine

    s = config["system"]
    return PagedLLMEngine(
        model_config(config), params, max_batch=s["max_batch"],
        max_len=s["max_len"], page_size=s["page_size"],
        num_pages=s["num_pages"], prefix_cache=s["prefix_cache"],
        kv_dtype=s["kv_dtype"])


def engine_counters(eng) -> dict:
    """The engine's counters, read field by field: its ``stats()`` also
    publishes the prefix digest, which iterates a dict the engine thread
    mutates, so it is not safe to call from the client's thread while the
    loop runs (PERF.md, open questions)."""
    return {"waiting": eng._waiting.qsize(),
            "active_slots": sum(r is not None for r in eng._active),
            "prefix_hit_pages": eng._prefix.hit_pages,
            "prefix_miss_pages": eng._prefix.miss_pages,
            "total_generated": eng.total_generated}


def live_bytes() -> int:
    """Bytes of live arrays on the fullest chip, from each array's shard
    shape (reading a shard's data would make a new live array of it, to
    be counted again the next time)."""
    import math

    import jax

    per = {}
    for a in jax.live_arrays():
        if a.is_deleted() or jax.dtypes.issubdtype(a.dtype,
                                                   jax.dtypes.prng_key):
            continue
        nbytes = (math.prod(a.sharding.shard_shape(a.shape))
                  * a.dtype.itemsize)
        for d in a.sharding.addressable_devices:
            per[d.id] = per.get(d.id, 0) + nbytes
    return max(per.values()) if per else 0


def _stats_bytes(m) -> int:
    return int(m.argument_size_in_bytes + m.temp_size_in_bytes
               - m.alias_size_in_bytes)


def program_bytes(compiled) -> int:
    """What a compiled program needs of a chip while it runs, as the
    compiler counts it: its arguments and its temporaries, less what the
    donated arguments and the outputs share."""
    return _stats_bytes(compiled.memory_analysis())


def largest_program() -> tuple:
    """(bytes, name) of the most demanding program this process has
    compiled or read from the compile cache and still holds, whoever
    compiled it: the backend lists its live executables, so the benchmark
    needs no handle on the program's jitted functions."""
    import jax

    best = (0, "none")
    for ex in jax.devices()[0].client.live_executables():
        try:
            nbytes = _stats_bytes(ex.get_compiled_memory_stats())
        except Exception:  # noqa: BLE001 - a backend with no such report
            continue
        if nbytes > best[0]:
            try:
                name = ex.hlo_modules()[0].name
            except Exception:  # noqa: BLE001 - the name is for reading only
                name = "unnamed"
            best = (nbytes, name)
    return best
