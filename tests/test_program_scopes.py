"""The device programs name their pieces (PR 57): one vocabulary of
``jax.named_scope``s (``ops/scopes.py``) over the seven families' blocks,
the engine's two programs and the train step; the innermost-name rule and
the reading of a compiled module's text (``util/program_scopes.py``); and
the record a traced engine leaves when it stops. All on the CPU with the
tiny configurations."""

import re
import time
from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import (dots3_note, falcon_h1, laguna, llama, nemotron_h,
                            olmoe, smallthinker)
from ray_tpu.ops import scopes
from ray_tpu.ops.moe import DENSE_MAX_TOKENS
from ray_tpu.serve.engine_programs import EnginePrograms
from ray_tpu.serve.paged_llm import PagedLLMEngine
from ray_tpu.train.trainer import JaxTrainer, TrainConfig
from ray_tpu.util import program_scopes, tracing

PAGE, SLOTS, PAGES = 16, 4, 64
# what every engine program names, whatever the model; what a routed
# feed-forward adds (``moe_dispatch`` and ``moe_combine`` are the grouped
# formulation's rows in and out, and the layer's own reshape and residual
# sum); and what no engine program may hold
ENGINE = {scopes.EMBED, scopes.NORM, scopes.ATTN_QKV, scopes.KV_WRITE,
          scopes.ATTN_OUT, scopes.LM_HEAD, scopes.SAMPLE}
ROUTED = {scopes.MOE_ROUTER, scopes.MOE_DISPATCH, scopes.MOE_EXPERTS,
          scopes.MOE_COMBINE}
MIXER = {scopes.SSM_MIXER}
TRAIN = {scopes.LOSS, scopes.OPTIMIZER}
# family -> (module, tiny configuration, the names its plan adds)
FAMILIES = {
    "llama": (llama, llama.llama_tiny, {scopes.ATTN, scopes.FFN}),
    "olmoe": (olmoe, olmoe.olmoe_tiny, {scopes.ATTN} | ROUTED),
    "laguna": (laguna, laguna.laguna_tiny,
               {scopes.ATTN, scopes.FFN, scopes.SHARED_EXPERT} | ROUTED),
    "smallthinker": (smallthinker, smallthinker.smallthinker_tiny,
                     {scopes.ATTN} | ROUTED),
    "falcon_h1": (falcon_h1, falcon_h1.falcon_h1_tiny,
                  {scopes.ATTN, scopes.FFN} | MIXER),
    "nemotron_h": (nemotron_h, nemotron_h.nemotron_h_tiny,
                   {scopes.ATTN, scopes.SHARED_EXPERT} | ROUTED | MIXER),
    "dots3_note": (dots3_note, dots3_note.dots3_note_tiny,
                   {scopes.LATENT_ATTN, scopes.INDEX_SELECT, scopes.FFN,
                    scopes.SHARED_EXPERT} | ROUTED),
}
_PATH = re.compile(r'op_name="([^"]*)"')


def engine_programs(family):
    """The family's tiny configuration's engine programs over weights
    that are shapes alone: nothing runs."""
    module, tiny, _ = FAMILIES[family]
    cfg = tiny()
    params = jax.eval_shape(partial(module.init_params, cfg),
                            jax.random.key(0))
    return EnginePrograms(cfg, params, max_batch=SLOTS, num_pages=PAGES,
                          page_size=PAGE, kv_dtype="bf16")


def lowered(programs, kind):
    """A cold prefill of one 512-token prompt (past ``DENSE_MAX_TOKENS``:
    the routed experts' grouped formulation), or a decode chunk of two
    steps, lowered as the engine would dispatch it."""
    def zeros(*dims, dtype=jnp.int32):
        return jnp.zeros(dims, dtype)

    key = jax.random.key(0)
    if kind == "prefill":
        tokens, wide = 2 * DENSE_MAX_TOKENS, 2 * DENSE_MAX_TOKENS // PAGE
        program, arguments = programs.prefill(
            wide, table_rows=zeros(1, wide), tokens=zeros(1, tokens),
            slens=zeros(1), starts=zeros(1), temps=zeros(1, dtype=jnp.float32),
            key=key, slots=zeros(1))
    else:
        program, arguments = programs.decode(
            2, 8, table=zeros(SLOTS, 8), tokens=zeros(SLOTS),
            lengths=zeros(SLOTS), active=zeros(SLOTS, dtype=jnp.bool_),
            temps=zeros(SLOTS, dtype=jnp.float32), key=key)
    return program.lower(*arguments)


def compiled_scopes(compiled_text) -> set:
    """The scopes of a compiled program's instructions (here the CPU's),
    those inside its fusions too, each by its own ``op_name``."""
    return {program_scopes.scope_of(path)
            for path in _PATH.findall(compiled_text)} - {""}


# -- the vocabulary -------------------------------------------------------

def test_the_vocabulary_is_stated_once_and_every_scope_is_of_it():
    """About twenty names, no more than twenty-four, each a constant of
    ``ops/scopes.py``; and no ``named_scope(`` under ``ray_tpu/`` is handed
    a string of its own."""
    import pathlib

    names = scopes.VOCABULARY
    assert 18 <= len(names) <= 24 and len(set(names)) == len(names)
    constants = {v for k, v in vars(scopes).items()
                 if k.isupper() and isinstance(v, str)}
    assert constants == set(names)
    root = pathlib.Path(scopes.__file__).parents[1]
    uses = [line.strip() for path in root.rglob("*.py")
            for line in path.read_text().splitlines()
            if "named_scope(" in line and not line.lstrip().startswith(
                ("#", '"', "`", "lowered"))]
    assert len(uses) > 60
    stray = [u for u in uses if not re.search(
        r"jax\.named_scope\(scopes\.[A-Z_]+\)", u)]
    assert not stray, stray


@pytest.mark.parametrize("kind", ["prefill", "decode"])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_familys_programs_name_the_pieces_its_plan_has(family, kind):
    """Both programs of each family, compiled, hold at least one
    instruction under every name the family's plan should have and none
    under a name it should not: a routed family all four ``moe_*`` (a
    prefill past ``DENSE_MAX_TOKENS`` rows sorts, gathers and sums; a
    decode step's ``moe_dispatch`` is a reshape, which compiles to
    nothing), Falcon-H1 and Nemotron-H ``ssm_mixer`` with the scan
    inside it in a prefill and the step in a decode, dots3-note
    ``latent_attn`` and ``index_select``; never the train step's."""
    found = compiled_scopes(
        lowered(engine_programs(family), kind).compile().as_text())
    expected = ENGINE | FAMILIES[family][2]
    if scopes.SSM_MIXER in expected:
        expected = expected | {scopes.SSM_SCAN if kind == "prefill"
                               else scopes.SSM_STEP}
    if kind == "decode":
        expected = expected - {scopes.MOE_DISPATCH}
    assert found == expected, (sorted(found - expected),
                               sorted(expected - found))
    assert not found & TRAIN


def test_a_short_prefill_has_no_rows_to_sort():
    """Up to ``DENSE_MAX_TOKENS`` rows every held expert runs over every
    row: the grouped formulation's sort and gathers are not in the
    program, and what is left under ``moe_dispatch`` and ``moe_combine``
    is the layer's reshape and its residual sum."""
    programs = engine_programs("olmoe")
    key = jax.random.key(0)
    z = partial(jnp.zeros, dtype=jnp.int32)
    program, arguments = programs.prefill(
        4, table_rows=z((1, 4)), tokens=z((1, 64)), slens=z((1,)),
        starts=z((1,)), temps=jnp.zeros((1,)), key=key, slots=z((1,)))
    paths = _PATH.findall(program.lower(*arguments).compile().as_text())
    def sorts(paths):
        return [p for p in paths if "moe_dispatch/jit(argsort)/sort" in p]

    def gathers(paths):
        return [p for p in paths if "moe_combine/gather" in p]

    assert not sorts(paths) and not gathers(paths)
    assert [p for p in paths if "moe_experts/" in p
            and p.endswith("dot_general")]
    cold = lowered(programs, "prefill").compile().as_text()
    assert sorts(_PATH.findall(cold)) and gathers(_PATH.findall(cold))


def test_the_train_step_names_its_loss_and_its_optimizer():
    """``JaxTrainer``'s step: the model's pieces under the forward's and
    the backward's transformations, the fused loss, the optimizer."""
    cfg = llama.llama_tiny()
    trainer = JaxTrainer(cfg, TrainConfig(
        mesh_axes={"dp": -1}, strategy="dp", fused_loss=True,
        warmup_steps=1))
    state = trainer.abstract_state()
    batch = jnp.zeros((8, 33), jnp.int32)
    text = trainer.compile_step(state, batch).lower(
        state, batch).compile().as_text()
    assert compiled_scopes(text) == {
        scopes.EMBED, scopes.NORM, scopes.ATTN_QKV, scopes.ATTN,
        scopes.ATTN_OUT, scopes.FFN, scopes.LOSS, scopes.OPTIMIZER}
    # the backward pass keeps the forward's names: a layer's under the
    # transformed loop, the loss's own under its transformed scope
    paths = _PATH.findall(text)
    assert [p for p in paths if p.startswith("jit(_step)/transpose(jvp())/")
            and program_scopes.scope_of(p) == scopes.ATTN_QKV]
    assert [p for p in paths if "/transpose(jvp(loss))/" in p]


# -- the innermost-name rule ----------------------------------------------

@pytest.mark.parametrize("path,scope", [
    ("jit(f)/while/body/closed_call/attn_qkv/dot_general", "attn_qkv"),
    ("jit(f)/ssm_mixer/ssm_scan/while/body/mul", "ssm_scan"),
    ("jit(f)/ssm_mixer/dot_general", "ssm_mixer"),
    ("jit(f)/attn_qkv/norm/rsqrt", "norm"),
    ("jit(f)/moe_experts/moe_combine/reduce_sum", "moe_combine"),
    ("jit(f)/transpose(jvp(attn_qkv))/dot_general", "attn_qkv"),
    ("jit(f)/jvp(ssm_mixer)/ssm_scan/while/body/closed_call/tanh",
     "ssm_scan"),
    ("jit(f)/transpose(jvp(loss))/while/body/checkpoint/mul", "loss"),
    ("jit(f)/vmap(attn)/dot_general", "attn"),
    # a jitted FUNCTION that happens to be called ``norm`` is no scope
    ("jit(f)/jit(norm)/sqrt", ""),
    ("jit(f)/ffn/jit(norm)/sqrt", "ffn"),
    ("jit(attn)/mul", ""),
    ("jit(main)/while/body/add", ""),
    ("attention/dot_general", ""),          # not of the vocabulary
    ("", ""),
])
def test_an_instructions_scope_is_the_innermost_name_on_its_path(path,
                                                                 scope):
    assert program_scopes.scope_of(path) == scope


def test_a_compiled_modules_text_gives_each_instruction_its_scope():
    """``instruction_scopes`` on a compiled CPU program with nested
    scopes under ``grad``: loop bodies are read, fused and applied
    computations are not, every instruction has its shape."""
    def f(x, w):
        with jax.named_scope(scopes.SSM_MIXER):
            y = x @ w
            with jax.named_scope(scopes.SSM_SCAN):
                y, _ = jax.lax.scan(lambda c, _: (jnp.tanh(c @ w), None), y,
                                    None, length=3)
        with jax.named_scope(scopes.NORM):
            y = jnp.linalg.norm(y) * y
        return y.sum()

    x = jnp.ones((8, 8))
    text = jax.jit(jax.grad(f)).lower(x, x).compile().as_text()
    found, _ = program_scopes.instruction_scopes(text)
    said = {scope for _, scope in found.values()}
    assert {scopes.SSM_MIXER, scopes.SSM_SCAN, scopes.NORM} <= said
    assert said <= {scopes.SSM_MIXER, scopes.SSM_SCAN, scopes.NORM, ""}
    in_loops = [name for name, (_, scope) in found.items()
                if scope == scopes.SSM_SCAN]
    assert len(in_loops) >= 2           # the forward's and the backward's
    # a fused computation's instructions are not the module's own
    fused = re.findall(r"^%(fused_computation[\w.]*) ", text, re.M)
    inner = re.findall(r"^  (?:ROOT )?%(\S+) = ", text.split(
        f"%{fused[0]} ", 1)[1].split("\n}", 1)[0], re.M)
    assert inner and not set(inner) & set(found)
    assert all(re.fullmatch(r"[a-z]+[0-9]*\[[0-9,]*\]", shape)
               for shape, _ in found.values())


_MODULE = """HloModule jit_f, is_scheduled=true

%fused_computation.1 (p0: bf16[8,4]) -> bf16[8,4] {
  %p0 = bf16[8,4]{1,0} parameter(0)
  %t.1 = bf16[8,4]{1,0} transpose(%p0), dimensions={0,1}, metadata={op_name="jit(f)/attn_qkv/concatenate"}
  ROOT %scatter.1 = bf16[8,4]{1,0} scatter(%p0, %t.1), to_apply=%region_1.1
}

%fused_computation.2 (p0: f32[8]) -> f32[8] {
  %p0.1 = f32[8]{0} parameter(0)
  %m.1 = f32[8]{0} multiply(%p0.1, %p0.1), metadata={op_name="jit(f)/while/body/moe_combine/mul"}
  ROOT %a.1 = f32[8]{0} add(%m.1, %p0.1), metadata={op_name="jit(f)/while/body/add"}
}

%region_1.1 (a: bf16[], b: bf16[]) -> bf16[] {
  %a = bf16[] parameter(0)
  ROOT %b = bf16[] parameter(1)
}

ENTRY %main.1 (x: f32[8], pool: bf16[8,4], rows: bf16[8,4]) -> (f32[8], bf16[8,4]) {
  %x = f32[8]{0} parameter(0)
  %pool = bf16[8,4]{1,0} parameter(1)
  %rows = bf16[8,4]{1,0:T(8,128)(2,1)} parameter(2)
  %index.1 = s32[8]{0:T(128)} fusion(%rows), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(f)/kv_write/select_n"}
  %fusion.9 = bf16[8,4]{1,0} fusion(%pool, %index.1), kind=kCustom, calls=%fused_computation.1
  %copy-start.1 = (f32[8]{0:S(1)}, f32[8]{0}, u32[]{:S(2)}) copy-start(%x)
  %copy-done.1 = f32[8]{0:S(1)} copy-done(%copy-start.1)
  %fusion.2 = f32[8]{0} fusion(%copy-done.1), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(f)/while/body/add"}
  %fusion.3 = f32[8]{0} fusion(%fusion.2), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(f)/while/body/moe_combine/mul"}
  %copy.3 = f32[8]{0} copy(%fusion.3)
  %slice-start.1 = ((f32[8]{0}), f32[4]{0:S(1)}, u32[]) slice-start(%copy.3), slice={[0:4]}, metadata={op_name="jit(f)/lm_head/slice"}
  ROOT %tuple.1 = (f32[8]{0}, bf16[8,4]{1,0}) tuple(%copy.3, %fusion.9)
}
"""


def test_what_the_compiler_made_takes_the_scope_of_what_it_moves():
    """The one rule: an instruction with no ``op_name`` at all (a fusion
    round a scatter the compiler rewrote, a prefetch's ``copy-start`` /
    ``copy-done``, a copy it put in) takes the scope of the first operand
    that has one, else of the first user, and the record says which took
    a scope so. An instruction whose OWN path holds no name of the
    vocabulary stays "", whatever its fused instructions or its
    neighbours say."""
    found, inferred = program_scopes.instruction_scopes(_MODULE)
    assert found["index.1"] == ["s32[8]", "kv_write"]
    # not ``attn_qkv``, which a fused instruction says: its operand's
    assert found["fusion.9"] == ["bf16[8,4]", "kv_write"]
    assert found["copy.3"] == ["f32[8]", "moe_combine"]
    assert inferred["fusion.9"] == inferred["copy.3"] == "operand"
    # named, by no scope: "" though what it fuses is ``moe_combine``'s
    assert found["fusion.2"] == ["f32[8]", ""]
    assert found["fusion.3"] == ["f32[8]", "moe_combine"]
    # the prefetch of ``x`` for an unnamed user stays unnamed
    assert found["copy-start.1"] == found["copy-done.1"] == ["f32[8]", ""]
    # a tuple in a tuple: the first shape still, as a trace event's name
    assert found["slice-start.1"] == ["f32[8]", "lm_head"]
    # (parameters and tuples take one too; they never run)
    assert set(inferred) == {"fusion.9", "copy.3", "tuple.1", "rows", "pool"}
    assert "m.1" not in found and "a" not in found and "t.1" not in found


def test_a_users_scope_names_what_nothing_before_it_does():
    """``"user"``: a prefetch whose operand is a parameter takes the scope
    of what reads it, through the ``copy-done`` between."""
    found, inferred = program_scopes.instruction_scopes(
        _MODULE.replace('calls=%fused_computation.2, metadata={op_name='
                        '"jit(f)/while/body/add"}',
                        'calls=%fused_computation.2, metadata={op_name='
                        '"jit(f)/ffn/add"}'))
    assert found["fusion.2"] == ["f32[8]", "ffn"]
    assert found["copy-done.1"] == found["copy-start.1"] == ["f32[8]", "ffn"]
    assert found["x"] == ["f32[8]", "ffn"]
    assert {inferred[n] for n in ("x", "copy-start.1", "copy-done.1")} == {
        "user"}


# -- the record a traced engine leaves -------------------------------------

@pytest.fixture(scope="module")
def tiny():
    cfg = llama.llama_tiny()
    return cfg, llama.init_params(cfg, jax.random.key(0))


def serve_once(tiny, traced: bool, monkeypatch):
    """A toy engine that serves one request untraced (its programs
    compile), then one with or without spans recorded, and stops."""
    cfg, params = tiny
    eng = PagedLLMEngine(cfg, params, max_batch=SLOTS, max_len=128,
                         page_size=PAGE, num_pages=40)
    eng.start()
    rng = np.random.default_rng(0)
    list(eng.submit(rng.integers(1, 500, 20), max_new_tokens=6).tokens())
    if traced:
        monkeypatch.setenv("RAY_TPU_TRACE_ENABLED", "1")
        time.sleep(0.25)            # ``is_enabled`` looks every 0.2 s
    list(eng.submit(rng.integers(1, 500, 21), max_new_tokens=6).tokens())
    monkeypatch.delenv("RAY_TPU_TRACE_ENABLED", raising=False)
    time.sleep(0.25)
    noted = set(eng._traced_programs)
    eng.stop()
    return eng, noted


def test_a_traced_engine_that_stops_leaves_its_programs_maps(tiny,
                                                             monkeypatch):
    """One ``program.scopes`` record for each executable of the programs
    dispatched while spans were recorded (the backend lists every
    executable of that module name it still holds), read by
    ``tracing.recorded_scopes()``; taken at ``stop()``, when nothing
    records any more, and bounded: a second engine's stop replaces the
    records of the same executables."""
    monkeypatch.setattr(tracing, "_scope_maps", {})
    eng, noted = serve_once(tiny, True, monkeypatch)
    assert any(re.fullmatch(r"jit_paged_prefill_w\d+", n) for n in noted)
    assert any(re.fullmatch(r"jit_paged_decode_c\d+_w\d+", n) for n in noted)
    assert not eng._traced_programs and not tracing.recording()
    records = tracing.recorded_scopes()
    assert {r["program"] for r in records} == noted
    live = [ex for ex in jax.devices()[0].client.live_executables()
            if ex.hlo_modules()[0].name in noted]
    assert len(records) == len(live) >= len(noted)
    assert len({(r["program"], r["executable"]) for r in records}) == len(
        records)
    for r in records:
        assert set(r) == {"program", "executable", "scopes", "inferred"}
        assert set(r["inferred"].values()) <= {"operand", "user"}
        assert set(r["inferred"]) <= set(r["scopes"])
        said = {scope for _, scope in r["scopes"].values()}
        assert said <= set(scopes.VOCABULARY) | {""}
        assert {scopes.ATTN_QKV, scopes.ATTN, scopes.FFN,
                scopes.LM_HEAD} <= said
    (span,) = [s for s in tracing.recorded_spans("program.scopes")][-1:]
    assert span["attrs"]["executables"] == len(records)
    assert span["attrs"]["programs"] == len(noted)
    serve_once(tiny, True, monkeypatch)
    assert len(tracing.recorded_scopes()) == len(records)


def test_an_untraced_engine_records_nothing_and_asks_the_backend_nothing(
        tiny, monkeypatch):
    """With tracing off the engine notes no program and ``stop()`` does
    what it did: no call of ``live_executables``, no record."""
    def refuse(names):
        raise AssertionError(f"asked for the maps of {names}")

    monkeypatch.setattr(tracing, "_scope_maps", {})
    monkeypatch.setattr(program_scopes, "record_programs", refuse)
    eng, noted = serve_once(tiny, False, monkeypatch)
    assert noted == set() and eng.error is None
    assert tracing.recorded_scopes() == []


def test_fit_offers_the_train_steps_map(monkeypatch):
    """``JaxTrainer.fit`` records its step's map after the last step, if
    a step ran while spans were recorded, and nothing otherwise."""
    cfg = llama.llama_tiny()
    trainer = JaxTrainer(cfg, TrainConfig(
        mesh_axes={"dp": -1}, strategy="dp", fused_loss=True,
        warmup_steps=1))
    state = trainer.init_state(jax.random.key(0))
    batch = jnp.zeros((8, 33), jnp.int32)
    monkeypatch.setattr(tracing, "_scope_maps", {})
    state, _ = trainer.fit(state, iter([batch]), steps=1)
    assert tracing.recorded_scopes() == []
    monkeypatch.setenv("RAY_TPU_TRACE_ENABLED", "1")
    time.sleep(0.25)
    trainer.fit(state, iter([batch]), steps=1)
    monkeypatch.delenv("RAY_TPU_TRACE_ENABLED")
    records = tracing.recorded_scopes()
    assert records and {r["program"] for r in records} == {"jit__step"}
    said = {scope for r in records for _, scope in r["scopes"].values()}
    assert {scopes.LOSS, scopes.OPTIMIZER, scopes.ATTN_QKV} <= said
