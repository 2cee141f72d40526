"""The Falcon-H1 block (``models/falcon_h1.py``) and the paged engine's
recurrent state beside its KV pages (``serve/paged_llm.py``), at tiny
widths in float32 on the CPU: LOGITS against the family's plain reference
(``benchmark/families/falcon_h1.py``: the recurrence token by token), the
reference against the published implementation where ``transformers`` has
it, and what continuous batching owes a state that lives in a slot."""

import hashlib
import re
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference, serving
from benchmark.families import falcon_h1 as family
from engine_lowering import lower, programs_logits
from ray_tpu.models import falcon_h1, laguna
from ray_tpu.models.llama import LayerStack
from ray_tpu.ops import ssm
from ray_tpu.serve import engine_programs
from ray_tpu.serve.engine_programs import _model_module
from ray_tpu.serve.paged_llm import PagedLLMEngine

# the tiny model under the published key names: query groups of 5, two
# mixer groups, every multiplier another number than one
CONFIG = {
    "model_type": "falcon_h1", "vocab_size": 128, "hidden_size": 64,
    "num_hidden_layers": 2, "num_attention_heads": 10,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 96,
    "rope_theta": 10000.0, "rope_scaling": None, "rms_norm_eps": 1e-5,
    "mamba_d_ssm": 48, "mamba_n_heads": 6, "mamba_d_head": 8,
    "mamba_d_state": 16, "mamba_n_groups": 2, "mamba_d_conv": 4,
    "mamba_chunk_size": 8, "mamba_conv_bias": True, "mamba_proj_bias": False,
    "mamba_rms_norm": True, "mamba_norm_before_gate": False,
    "attention_bias": False, "mlp_bias": False, "projectors_bias": False,
    "embedding_multiplier": 2.5, "lm_head_multiplier": 0.25,
    "attention_in_multiplier": 1.5, "attention_out_multiplier": 0.6,
    "ssm_in_multiplier": 0.5, "ssm_out_multiplier": 0.8,
    "key_multiplier": 0.3, "mlp_multipliers": [0.7, 0.4],
    "ssm_multipliers": [0.9, 0.6, 0.7, 1.2, 0.8],
    "tie_word_embeddings": False, "torch_dtype": "float32"}
DEPARTURES = {
    "multipliers": {"multipliers": "none"},
    "gate_norm": {"gate_norm": "before"},
    "groups": {"groups": 1},
    "conv_bias": {"conv_bias": False},
    "key_multiplier": {"key_multiplier": 1},
}
# float32 against float32 over two blocks: the program's sums run in
# another order than the reference's (a chunked scan, fused matmuls)
LOGIT_TOL = 2e-4
# through the engine's programs the keys and values lie in bf16 pages
# whatever the model's dtype (the pool's format): attention's output
# carries their rounding, 0.006 at worst on unit logits here; a state
# installed, advanced or padded wrongly misses by 0.3 and more
PAGED_TOL = 2e-2


def make_params(cfg, seed=3):
    return falcon_h1.init_params(cfg, jax.random.key(seed))


@pytest.fixture(scope="module")
def tiny():
    cfg = family.model_config(CONFIG)
    return cfg, make_params(cfg)


def test_the_config_and_the_layer_plan(tiny):
    cfg, params = tiny
    assert cfg == falcon_h1.falcon_h1_tiny()
    assert cfg.conv_dim == 48 + 2 * 2 * 16
    (run,) = falcon_h1.layer_plan(cfg)
    assert isinstance(run, LayerStack)
    assert (run.key, run.kind, run.window, run.layers) == (None, "full",
                                                           None, 2)
    assert run.state.chunk == 8
    assert run.state.arrays == (
        ("ssm_state", (6, 8, 16), "float32"),
        ("conv_tail", (3, 112), "float32"))
    blocks = params["blocks"]
    assert blocks["wqkv"].shape == (2, 64, (10 + 2 * 2) * 16)
    assert blocks["in_proj"].shape == (2, 64, 48 + 112 + 6)
    assert blocks["conv_w"].shape == (2, 112, 4)
    assert blocks["out_proj"].shape == (2, 48, 64)
    assert {blocks[k].dtype for k in ("A_log", "dt_bias", "D")} == {
        jnp.dtype("float32")}
    # the published model's plan: 72 layers of one run, the state the
    # issue sizes the engine by
    (big,) = falcon_h1.layer_plan(falcon_h1.falcon_h1_34b_instruct())
    assert big.layers == 72 and big.state.chunk == 128
    assert big.state.arrays == (
        ("ssm_state", (32, 128, 256), "float32"),
        ("conv_tail", (3, 5120), "bfloat16"))
    with pytest.raises(ValueError, match="mamba_d_ssm"):
        falcon_h1.falcon_h1_tiny(ssm_heads=5)
    # a model that repeats one block and keeps pages only states no state
    from ray_tpu.models import llama
    assert llama.layer_plan(llama.llama_tiny())[0].state is None
    assert all(run.state is None
               for run in laguna.layer_plan(laguna.laguna_tiny()))


def test_the_seeded_weights_leave_unit_logits_under_the_multipliers(tiny):
    """The init draws each matrix at the fan-in scale OVER the multiplier
    that follows it (``init_params``'s note): the logits have about unit
    variance, so the reference check's 0.1 means what it means for the
    other families."""
    cfg, params = tiny
    toks = jax.random.randint(jax.random.key(1), (2, 40), 1, cfg.vocab_size)
    spread = float(jnp.std(falcon_h1.forward(cfg, params, toks)))
    assert 0.5 < spread < 2.0


@pytest.mark.parametrize("tokens", [1, 7, 8, 21, 40])
def test_forward_is_the_familys_reference(tiny, tokens):
    cfg, params = tiny
    toks = jax.random.randint(jax.random.key(tokens), (2, tokens), 1,
                              cfg.vocab_size)
    got = falcon_h1.forward(cfg, params, toks)
    want = family.logits(CONFIG, params, toks)
    assert got.shape == want.shape == (2, tokens, cfg.vocab_size)
    np.testing.assert_allclose(got, want, rtol=LOGIT_TOL, atol=LOGIT_TOL)


@pytest.mark.parametrize("name", sorted(DEPARTURES))
def test_each_departure_of_the_reference_is_another_model(tiny, name):
    cfg, params = tiny
    toks = jax.random.randint(jax.random.key(7), (2, 30), 1, cfg.vocab_size)
    want = family.logits(CONFIG, params, toks)
    other = family.logits(CONFIG, params, toks, **DEPARTURES[name])
    assert float(jnp.max(jnp.abs(other - want))) > 0.3


def _steps(cfg, p, x, tokens, rows=2):
    """``tokens`` tokens one at a time through ``recurrent_step`` from
    the zero state, the rows' states at layer 1 of a stack of two:
    (each step's term, the rows' state and tail at the end)."""
    state = tuple(jnp.stack([jnp.full_like(a, 3.0), a])
                  for a in falcon_h1.zero_state(cfg, rows))
    active = jnp.ones((rows,), bool)
    terms = []
    for t in range(tokens):
        term, state = falcon_h1.recurrent_step(cfg, p, x[:, t:t + 1], state,
                                               jnp.int32(1), active)
        terms.append(term[:, 0])
    # the other layer's arrays are as they were
    for a in state:
        assert float(jnp.min(a[0])) == float(jnp.max(a[0])) == 3.0
    return terms, tuple(a[1] for a in state)


def test_the_two_forms_of_the_mixer_agree(tiny):
    """A block of tokens through ``recurrent_mixer`` and the same tokens
    one at a time through ``recurrent_step`` (at one layer of a stack of
    states, as the engine hands them over): the same terms for the
    stream, and the same state and tail at the end."""
    cfg, params = tiny
    p = jax.tree.map(lambda a: a[0], params["blocks"])
    x = jax.random.normal(jax.random.key(2), (2, 16, cfg.d_model))
    valid = jnp.ones((2, 16), bool)
    out, (s_end, tail_end) = falcon_h1.recurrent_mixer(
        cfg, p, x, falcon_h1.zero_state(cfg, 2), valid)
    terms, state = _steps(cfg, p, x, 16)
    for t, step in enumerate(terms):
        np.testing.assert_allclose(step, out[:, t], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(state[0], s_end, rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(state[1]), np.asarray(tail_end))
    # padding behind 5 valid tokens: the state and tail after the fifth
    out5, (s5, tail5) = falcon_h1.recurrent_mixer(
        cfg, p, x, falcon_h1.zero_state(cfg, 2), jnp.arange(16)[None] < 5)
    _, state = _steps(cfg, p, x, 5)
    np.testing.assert_allclose(state[0], s5, rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(state[1]), np.asarray(tail5))
    np.testing.assert_array_equal(np.asarray(out5[:, :5]),
                                  np.asarray(out[:, :5]))


def test_a_step_leaves_an_inactive_slots_state_and_tail(tiny):
    """``recurrent_step`` over three slots of which the middle one is
    inactive: its state and its tail at the layer are the bits they
    were, the others' moved."""
    cfg, params = tiny
    p = jax.tree.map(lambda a: a[1], params["blocks"])
    ks = jax.random.split(jax.random.key(5), 3)
    x = jax.random.normal(ks[0], (3, 1, cfg.d_model))
    (_, s_shape, _), (_, t_shape, t_dt) = \
        falcon_h1.layer_plan(cfg)[0].state.arrays
    state = (jax.random.normal(ks[1], (2, 3, *s_shape)),
             jax.random.normal(ks[2], (2, 3, *t_shape)).astype(t_dt))
    _, new = falcon_h1.recurrent_step(cfg, p, x, state, jnp.int32(0),
                                      jnp.array([True, False, True]))
    for before, after in zip(state, new):
        before, after = np.asarray(before), np.asarray(after)
        np.testing.assert_array_equal(after[1], before[1])
        np.testing.assert_array_equal(after[0, 1], before[0, 1])
        assert not np.array_equal(after[0, 0], before[0, 0])
        assert not np.array_equal(after[0, 2], before[0, 2])


# -- the engine's two programs against the reference's one forward pass ------

def _programs_logits(monkeypatch, cfg, params, prompt, new, *, page,
                     chunk=4):
    """The logits the engine's two programs compute for ``prompt`` and
    ``new`` greedy tokens behind it (``engine_lowering.programs_logits``:
    its state installed in slot 1 of three, the others inactive and
    checked to be left as they were)."""
    rows, tokens, _ = programs_logits(monkeypatch, cfg, params, [prompt], new,
                                      page=page, slots=(1,), chunk=chunk)
    return rows[0], tokens[0]


@pytest.mark.parametrize("plen,chunk_len,page", [
    (1, 128, 128), (127, 128, 128), (128, 128, 128), (129, 128, 128),
    (21, 8, 8), (40, 8, 16)],
    ids=["len1", "len127", "len128", "len129", "padded-bucket",
         "chunk-under-page"])
def test_prefill_then_decode_is_the_references_forward_pass(
        monkeypatch, plen, chunk_len, page):
    """Prompt lengths round the scan's chunk and the page (127, 128, 129
    at a chunk of 128 tokens: one chunk less a token, one whole chunk,
    two chunks with 127 padded positions), one token, and a bucket with
    padding over several short chunks: the prefill program's logits and
    eight decode steps' are the rows of the reference's ONE forward pass
    over the prompt and the tokens the programs chose."""
    config = dict(CONFIG, mamba_chunk_size=chunk_len)
    cfg = family.model_config(config)
    params = make_params(cfg)
    prompt = np.random.default_rng(plen).integers(1, cfg.vocab_size, plen)
    new = 9
    got, tokens = _programs_logits(monkeypatch, cfg, params, prompt, new,
                                   page=page)
    seq = np.concatenate([prompt, tokens[:-1]])[None]
    want = np.asarray(family.logits(config, params, seq))[0, plen - 1:]
    assert got.shape == want.shape == (new, cfg.vocab_size)
    np.testing.assert_allclose(got, want, rtol=PAGED_TOL, atol=PAGED_TOL)
    gap, _ = reference.token_gap(family.logits, config, params, prompt,
                                 tokens)
    assert gap <= PAGED_TOL


@pytest.mark.parametrize("plen,chunk", [(21, 4), (8, 3)],
                         ids=["padded-bucket", "one-chunk-of-prompt"])
def test_prefill_then_decode_through_the_state_kernel(monkeypatch, plen,
                                                      chunk):
    """The same check with the decode program's state update in the
    KERNEL (interpret mode; a state of 128, whole lanes, so that the rule
    holds and the engine's own arrays are what the kernel takes): the
    state a prefill installed, advanced in place at [layer, slot] beside
    two inactive slots, gives the reference's forward pass, and the
    inactive slots' arrays are the bits they were (``_programs_logits``
    checks)."""
    config = dict(CONFIG, mamba_n_heads=8, mamba_d_ssm=64,
                  mamba_d_state=128)
    cfg = family.model_config(config)
    params = make_params(cfg)
    calls = []

    def kernel(x, dt, a, b, c, states, layer, active):
        assert ssm.state_kernel_engages(states)
        calls.append(states.shape)
        return ssm.ssm_state_step_kernel(x, dt, a, b, c, states, layer,
                                         active, interpret=True)

    monkeypatch.setattr(falcon_h1, "ssm_state_step", kernel)
    prompt = np.random.default_rng(plen).integers(1, cfg.vocab_size, plen)
    new = 9
    got, tokens = _programs_logits(monkeypatch, cfg, params, prompt, new,
                                   page=8, chunk=chunk)
    assert calls and set(calls) == {(2, 3, 8, 8, 128)}
    seq = np.concatenate([prompt, tokens[:-1]])[None]
    want = np.asarray(family.logits(config, params, seq))[0, plen - 1:]
    np.testing.assert_allclose(got, want, rtol=PAGED_TOL, atol=PAGED_TOL)
    gap, _ = reference.token_gap(family.logits, config, params, prompt,
                                 tokens)
    assert gap <= PAGED_TOL


# -- continuous batching over a state that lives in a slot --------------------

def _alone(cfg, params, prompt, new):
    toks = list(prompt)
    for _ in range(new):
        lg = falcon_h1.forward(cfg, params, jnp.asarray(toks)[None])
        toks.append(int(jnp.argmax(lg[0, -1])))
    return toks[len(prompt):]


@pytest.fixture(scope="module")
def prompts(tiny):
    cfg, _ = tiny
    rng = np.random.default_rng(0)
    return [rng.integers(1, cfg.vocab_size, n) for n in (9, 30, 1, 17)]


@pytest.fixture(scope="module")
def alone(tiny, prompts):
    """Each prompt's first 16 greedy tokens by the model's plain forward
    pass, once for the tests below (a shorter budget is a prefix)."""
    return [_alone(*tiny, prompt, 16) for prompt in prompts]


def test_a_refilled_slot_starts_from_its_new_tenants_state(tiny, prompts,
                                                           alone):
    """One slot, four requests one after another, and between two of them
    the slot's state POISONED (every entry NaN, as the worst a tenant
    that decoded on past its end could leave): each tenant gets the
    tokens it gets alone. Its prefill installs its state whole and reads
    nothing of what was there."""
    cfg, params = tiny
    eng = PagedLLMEngine(cfg, params, max_batch=1, max_len=64, page_size=8,
                         num_pages=16)
    eng.start()
    try:
        for i, prompt in enumerate(prompts):
            got = serving.collect(eng, eng.submit(prompt, max_new_tokens=10))
            assert got == alone[i][:10], i
            if i == 1:
                deadline = 200
                while eng.stats()["active_slots"] and deadline:
                    deadline -= 1
                # the loop is idle: nothing is in flight to donate these
                eng._programs.state = tuple(
                    jnp.full_like(a, jnp.nan) for a in eng._programs.state)
        stats = eng.stats()
    finally:
        eng.stop()
    assert stats["state_installs"] == len(prompts)
    per_slot = 4 * 6 * 8 * 16 + 4 * 3 * 112
    assert stats["state_bytes_held"] == cfg.n_layers * per_slot


def test_interleaved_requests_each_get_their_own_tokens(tiny, prompts,
                                                        alone):
    """Two slots, four requests of different lengths and budgets handed
    over at once: slots retire and refill while their neighbours decode,
    and every request gets the tokens it gets alone."""
    cfg, params = tiny
    eng = PagedLLMEngine(cfg, params, max_batch=2, max_len=64, page_size=8,
                         num_pages=24)
    budgets = [12, 5, 16, 8]
    eng.start()
    try:
        reqs = [eng.submit(p, max_new_tokens=n)
                for p, n in zip(prompts, budgets)]
        got = [serving.collect(eng, r) for r in reqs]
    finally:
        eng.stop()
    for want, n, tokens in zip(alone, budgets, got):
        assert tokens == want[:n]
    assert eng.stats()["state_installs"] == 4


def test_a_handed_over_slots_state_is_its_new_tenants(tiny, prompts, alone):
    """One slot, two requests waiting before the loop starts: A's end is
    foreseen where its last chunk is dispatched, and B's prefill goes out
    behind that chunk, before A's last tokens are read. On the device
    stream B's state is installed after A's last step wrote the slot's,
    so each gets the tokens it gets alone, A its last ones too."""
    cfg, params = tiny
    eng = PagedLLMEngine(cfg, params, max_batch=1, max_len=64, page_size=8,
                         num_pages=16, decode_chunk=4)
    budgets = [7, 5]
    reqs = [eng.submit(p, max_new_tokens=n)
            for p, n in zip(prompts, budgets)]
    eng.start()
    try:
        got = [serving.collect(eng, r) for r in reqs]
        stats = eng.stats()
    finally:
        eng.stop()
    for want, n, tokens in zip(alone, budgets, got):
        assert tokens == want[:n]
    assert (stats["slots_handed_over"], stats["state_installs"]) == (1, 2)
    assert stats["decode_overrun_ahead"] == 0


def test_a_prefix_hit_is_impossible_by_rule(tiny):
    cfg, params = tiny
    with pytest.raises(ValueError, match="without the recurrent state"):
        PagedLLMEngine(cfg, params, max_batch=1, max_len=64, page_size=8,
                       prefix_cache=True)
    # left unset it follows the flag only for a plan of pages alone
    eng = PagedLLMEngine(cfg, params, max_batch=1, max_len=64, page_size=8)
    assert eng.stats()["prefix_cache"]["enabled"] is False
    assert [a.shape for a in eng._programs.state] == [
        (2, 1, 6, 8, 16), (2, 1, 3, 112)]


def test_a_plan_without_a_recurrent_run_allocates_and_passes_nothing():
    from ray_tpu.models import llama

    cfg = llama.llama_tiny()
    eng = PagedLLMEngine(cfg, llama.init_params(cfg, jax.random.key(0)),
                         max_batch=2, max_len=64, page_size=16)
    programs = eng._programs
    assert programs.state == ()
    for order in (engine_programs._DECODE, engine_programs._PREFILL):
        # the four pools follow the weights, and nothing follows the key
        assert order.donated(len(programs.pools), 0) == (1, 2, 3, 4)
        inputs = dict.fromkeys(order.inputs + order.beside_state)
        assert len(order.arguments(None, programs.pools, inputs, ())) == (
            1 + 4 + len(order.inputs))
    stats = eng.stats()
    assert stats["state_installs"] == 0 and stats["state_bytes_held"] == 0
    assert stats["prefix_cache"]["enabled"] is True     # the flag's default


def test_the_module_is_found_by_the_configs_class_and_checked(tiny):
    cfg, _ = tiny
    assert _model_module(cfg) is falcon_h1
    assert _model_module(laguna.laguna_tiny()) is laguna
    # a config whose module states no block: the error names what is missing
    with pytest.raises(TypeError, match="states no layer_plan"):
        _model_module(types.SimpleNamespace())
    # a recurrent plan needs the mixer's two forms
    half = types.ModuleType("half_a_model")
    for name in engine_programs._PIECES:
        setattr(half, name, getattr(falcon_h1, name))
    half.Config = type("Config", (falcon_h1.FalconH1Config,),
                       {"__module__": "half_a_model"})
    sys.modules["half_a_model"] = half
    try:
        with pytest.raises(TypeError, match="recurrent_mixer, recurrent_step"):
            _model_module(half.Config(**vars(cfg)))
    finally:
        del sys.modules["half_a_model"]


# -- the older families' programs are the parent's ---------------------------

# sha256 (first 16 hex digits) of the text each Laguna engine program
# lowered to on the parent commit (ee80ec6), computed by
# the helper that lowered them then laid over that tree under the jax named
# below (``engine_lowering.lower`` lowers the same text now).
# ``tests/test_fused_projections.py`` holds the Llama and OLMoE programs'
# digests, which this PR leaves as they are.
_LAGUNA_PARENT_TEXT = {
    ("decode", (4, 4), "bf16"): "c033d51019da2ce3",
    ("decode", (4, 4), "int8"): "93775a777c13e839",
    ("prefill", (2, 16, 4), "bf16"): "619c688386e6b643",
    ("prefill", (2, 16, 4), "int8"): "c93d1f2b4ebd1171",
    ("prefill", (2, 64, 8), "bf16"): "960d7dd8c82f4a77",
    ("prefill", (2, 64, 8), "int8"): "0888b06caf7ac822",
}
_PINNED_JAX = "0.9.0"


@pytest.mark.parametrize("program,dims,kv_dtype", sorted(_LAGUNA_PARENT_TEXT),
                         ids=lambda v: str(v).replace(" ", ""))
def test_lagunas_engine_programs_lower_to_the_parents_text(program, dims,
                                                           kv_dtype):
    """A plan without a recurrent run gives the engine's programs no new
    argument, carry or operation: byte for byte the parent's text."""
    if jax.__version__ != _PINNED_JAX:
        pytest.skip(f"digests pinned under jax {_PINNED_JAX}")
    text = lower(jax.devices("cpu")[0], laguna, laguna.laguna_tiny(), program,
                 dims, num_pages=16, slots=4, page=8,
                 kv_dtype=kv_dtype).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        _LAGUNA_PARENT_TEXT[(program, dims, kv_dtype)]


def test_a_recurrent_programs_state_is_donated_and_aliased(tiny):
    """The slots' state goes into both programs donated and comes back
    in place, as the pools do: every state argument is aliased to an
    output."""
    cfg, _ = tiny
    for program, dims in (("decode", (4, 4)), ("prefill", (2, 16, 4))):
        lowered = lower(jax.devices("cpu")[0], falcon_h1, cfg, program, dims,
                        num_pages=16, slots=4, page=8)
        main = next(line for line in lowered.as_text().splitlines()
                    if "func.func public @main" in line)
        for shape in ("2x4x6x8x16xf32", "2x4x3x112xf32"):
            (arg,) = re.findall(rf"%arg\d+: tensor<{shape}> \{{[^%]*", main)
            assert "tf.aliasing_output" in arg, (program, shape)


# -- the reference against the published implementation ----------------------

def test_the_reference_is_the_published_implementation():
    """``FalconH1ForCausalLM`` (transformers' own ``modeling_falcon_h1``,
    its plain torch path on the CPU) on weights copied from this repo's
    layout gives the family's logits to float32 rounding: the reference
    is the published code's mathematics, multipliers, gated grouped norm,
    convolution and recurrence included."""
    torch = pytest.importorskip("torch")
    hf = pytest.importorskip("transformers.models.falcon_h1")
    keys = {k: v for k, v in CONFIG.items()
            if k not in ("model_type", "torch_dtype", "head_dim")}
    hf_cfg = hf.FalconH1Config(
        **keys, head_dim=CONFIG["head_dim"], mamba_expand=2,
        attention_dropout=0.0, hidden_act="silu", mamba_use_mlp=True,
        max_position_embeddings=256, attn_implementation="eager")
    model = hf.FalconH1ForCausalLM(hf_cfg).to(torch.float32).eval()
    cfg = family.model_config(CONFIG)
    params = make_params(cfg, seed=11)
    blocks = params["blocks"]
    qdim, kvdim = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim

    def t(a):
        return torch.tensor(np.asarray(a, np.float32))

    with torch.no_grad():
        model.model.embed_tokens.weight.copy_(t(params["embedding"]))
        model.lm_head.weight.copy_(t(params["lm_head"]).T)
        model.model.final_layernorm.weight.copy_(t(params["final_norm"]))
        for i, layer in enumerate(model.model.layers):
            p = jax.tree.map(lambda a: a[i], blocks)
            wqkv = t(p["wqkv"])
            layer.input_layernorm.weight.copy_(t(p["attn_norm"]))
            layer.pre_ff_layernorm.weight.copy_(t(p["mlp_norm"]))
            layer.self_attn.q_proj.weight.copy_(wqkv[:, :qdim].T)
            layer.self_attn.k_proj.weight.copy_(wqkv[:, qdim:qdim + kvdim].T)
            layer.self_attn.v_proj.weight.copy_(wqkv[:, qdim + kvdim:].T)
            layer.self_attn.o_proj.weight.copy_(t(p["wo"]).T)
            layer.mamba.in_proj.weight.copy_(t(p["in_proj"]).T)
            layer.mamba.conv1d.weight.copy_(t(p["conv_w"])[:, None, :])
            layer.mamba.conv1d.bias.copy_(t(p["conv_b"]))
            layer.mamba.dt_bias.copy_(t(p["dt_bias"]))
            layer.mamba.A_log.copy_(t(p["A_log"]))
            layer.mamba.D.copy_(t(p["D"]))
            layer.mamba.norm.weight.copy_(t(p["ssm_norm"]))
            layer.mamba.out_proj.weight.copy_(t(p["out_proj"]).T)
            layer.feed_forward.gate_proj.weight.copy_(t(p["w_gate"]).T)
            layer.feed_forward.up_proj.weight.copy_(t(p["w_up"]).T)
            layer.feed_forward.down_proj.weight.copy_(t(p["w_down"]).T)
    toks = np.random.default_rng(4).integers(1, cfg.vocab_size, (2, 27))
    with torch.no_grad():
        want = model(torch.tensor(toks), use_cache=False).logits.numpy()
    got = np.asarray(family.logits(CONFIG, params, jnp.asarray(toks)))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
