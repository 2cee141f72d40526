"""The ``granite_moe_hybrid`` model family of the benchmark (PR 62): its
file passes the family contract, the configuration is the published one
but for its cuts (the published keys written HERE, not read from a catalog
outside the repo), its counts are pinned at the published widths, its
predicates tell the mixer's and the experts' operations from the rest, the
two new readers (``ssm_step_share``, which has an entry, and
``lm_head_share``, which waits for one) read a synthetic trace by scope
and give nothing on another family's, the cell's entries keep the
contract, its traffic keeps every prompt in the 1,024 bucket and
the run at the 16-page table, each named departure of the reference alone
reads not correct at the toy size, and a toy configuration rehearses
``serve-assist-gen``'s runner on the CPU."""

import ast
import json
import os
import subprocess
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_pins  # noqa: E402
import bench_toy  # noqa: E402

from benchmark import (harness, program_scopes, reference,  # noqa: E402
                       serving, systems)
from benchmark.families import granite_moe_hybrid as family  # noqa: E402

ROOT = bench_toy.REPO
CELL = "serve-assist-gen"
CONFIG_NAME = "granite-4.0-h-small-ep2-d10"
# the older readers' entries, each with a cell it shares the entry with
TWINS = {"decode_program_step_ms": "serve-reason-gen",
         "decode_roofline": "serve-reason-gen",
         "prefill_program_share": "serve-reason-gen",
         "decode_active_share": "serve-reason-gen",
         "decode_delivered_share": "serve-reason-gen",
         "decode_overrun_share": "serve-reason-gen",
         "prefill_fill_share": "serve-reason-gen",
         "device_idle_share": "serve-reason-gen",
         "peak_hbm_gb": "serve-reason-gen",
         "engine_host_share": "serve-reason-gen",
         "paged_attn_roofline": "serve-reason-gen",
         "expert_ffn_share": "serve-reason-gen",
         "experts_touched_mean": "serve-reason-gen",
         "expert_load_max_over_mean": "serve-reason-gen",
         "routed_here_share": "serve-reason-gen",
         "ssm_mixer_share": "serve-reason-gen",
         "ssm_state_roofline": "serve-reason-gen",
         "prefill_scan_share": "serve-reason-gen",
         "prefill_expert_share": "serve-reason-gen",
         "prefill_ms_per_ktoken": "serve-reason-gen",
         "prefill_routed_share": "serve-reason-gen",
         "prefill_combine_share": "serve-reason-gen",
         "prefill_attn_share": "serve-reason-gen",
         "prefill_dense_share": "serve-reason-gen",
         "unscoped_share": "serve-reason-gen"}
OWN = {"ssm_step_share": "recurrent state"}
# a reader with no entry yet: ``tests/bench/test_bench_nemotron_h.py``
# holds ``per_layer`` under 64 entries on a copy with one entry more, a
# benchmark file this PR may not edit, so this PR adds ONE entry (62 + 1)
READERS = (*OWN, "lm_head_share")
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
# https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/main/
# config.json, the keys that say something about the model's shape
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.0078125,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 4096,
    "intermediate_size": 768, "layer_types": PERIOD * 4,
    "logits_scaling": 16, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 128,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 10,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 72, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 1536, "tie_word_embeddings": True,
    "vocab_size": 100352}
# the experts held are a cut too, stated under ``expert_share`` and not
# under the published key: ``bench_pins.CUTS``, which the accepted
# benchmark's own test holds every configuration to, knows no key of this
# family as a cut of experts
REDUCED = ["num_hidden_layers", "layer_types", "vocab_size"]


def cell_config() -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs",
                           CONFIG_NAME + ".json")) as f:
        return json.load(f)


# -- the family's file -------------------------------------------------------

def test_the_family_passes_the_api_check_and_keeps_off_the_program():
    assert systems.family({"family": "granite_moe_hybrid"}) is family
    path = os.path.join(ROOT, "benchmark", "families",
                        "granite_moe_hybrid.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    top = [n for n in tree.body if isinstance(n, (ast.Import, ast.ImportFrom))]
    names = [a.name for n in top if isinstance(n, ast.Import)
             for a in n.names] + [n.module for n in top
                                  if isinstance(n, ast.ImportFrom)]
    assert names and not any(n.split(".")[0] in ("ray_tpu", "benchmark")
                             for n in names)
    inner = {f.name for f in tree.body if isinstance(f, ast.FunctionDef)
             for n in ast.walk(f) if isinstance(n, ast.ImportFrom)
             and n.module.startswith("ray_tpu")}
    assert inner == {"model_config", "init_params"}
    assert family.train_flops_per_token(cell_config(), 2048) is None
    assert family.flash_train_cost(cell_config(), 2, 2048) is None
    # a greedy comparison cannot see ``logits_scaling``, and the file says so
    assert "``logits_scaling`` has no departure" in family.__doc__


def test_the_configuration_is_the_published_one_but_for_its_cuts():
    import jax

    config = cell_config()
    assert config["source"] == (
        "https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/main/"
        "config.json")
    differs = [k for k, v in PUBLISHED.items() if config.get(k, "") != v]
    assert sorted(differs) == sorted(REDUCED) and config["reduced"] == REDUCED
    assert {k: config["reduced_from"][k] for k in REDUCED
            if k != "layer_types"} == {
        k: PUBLISHED[k] for k in REDUCED if k != "layer_types"}
    # one period of layer_types: the first stage of four, 9 mixers to 1
    assert config["layer_types"] == PERIOD == PUBLISHED["layer_types"][:10]
    assert config["num_hidden_layers"] == 10
    assert family.layer_counts(config) == (9, 1)
    assert family.layer_counts(PUBLISHED) == (36, 4)
    assert config["expert_share"] == {"chips": 2, "index": 0,
                                      "num_experts_held": 36}
    assert family._share(config) == (72, 36, 0)
    # the floors: 8 experts a layer, an eighth of the vocabulary
    assert config["expert_share"]["num_experts_held"] == 36 >= 8
    assert config["vocab_size"] == 50176 == PUBLISHED["vocab_size"] // 2
    assert set(config["assumed"]) >= {
        "intermediate_size", "attention", "multipliers", "router", "experts",
        "mixer", "mamba_chunk_size", "state_dtype", "init"}
    for key in ("reduced_why", "deployment"):
        assert len(config[key]) > 200
    assert "4.757B" in config["reduced_why"] and "9.51 GB" in config[
        "reduced_why"]
    assert config["system"] == {
        "max_batch": 64, "max_len": 2048, "page_size": 128,
        "num_pages": 1280, "kv_dtype": "bf16", "prefix_cache": False,
        "reference_check": {"prompt_tokens": 960, "shared_tokens": 0,
                            "new_tokens": 32}}
    cfg = family.model_config(config)
    assert (cfg.n_experts, cfg.n_experts_held, cfg.first_expert,
            cfg.top_k) == (72, 36, 0, 10)
    assert (cfg.d_ssm, cfg.conv_dim, cfg.head_dim) == (8192, 8448, 128)
    assert (cfg.embedding_multiplier, cfg.attention_multiplier,
            cfg.residual_multiplier, cfg.logits_scaling,
            cfg.tie_embeddings) == (12.0, 1 / 128, 0.22, 16.0, True)
    shapes = jax.eval_shape(lambda: family.init_params(
        cfg, jax.random.key(0)))
    held = sum(a.size for a in jax.tree.leaves(shapes))
    assert held == family.total_params(config)
    with pytest.raises(ValueError, match="published Granite 4.0-H layers"):
        family.model_config(dict(config, position_embedding_type="rope"))


def test_counts_at_the_published_widths():
    m = cell_config()
    assert family.mixer_params(m) == 102_291_072     # its norm among them
    assert family.attention_params(m) == 41_947_136
    assert family.expert_params(m) == 9_437_184
    assert family.shared_expert_params(m) == 18_874_368
    assert family.router_params(m) == 294_912
    assert family.total_params(m) == 4_757_211_776            # "4.757B"
    assert 2 * family.total_params(m) / 1e9 == pytest.approx(9.51, abs=5e-3)
    whole = dict(PUBLISHED, expert_share=None)
    assert 32.1e9 < family.total_params(whole) < 32.3e9     # "32B"
    assert family.state_bytes_per_slot_layer(m) == 4 * 128 * 64 * 128 \
        + 2 * 3 * 8448
    assert family.kv_bytes_per_token_layer(m) == 4096


def test_decode_step_bytes_at_the_cells_sizes():
    m = cell_config()
    full = {"occupancy_samples": [64] * 4, "live_kv_tokens_mean": 64 * 1200.0}
    total = family.decode_step_bytes(m, full)
    state, kv = family.ssm_state_bytes(m, full), family.attention_kv_bytes(
        m, full)
    assert state == pytest.approx(2 * 9 * 64 * 4_244_992)       # 4.89 GB
    assert kv == pytest.approx(64 * 1200 * 4096)                # 0.31 GB
    assert total == pytest.approx(14.7246e9, rel=1e-4)
    experts = 2.0 * 10 * 36 * family.expert_params(m)           # 6.79 GB
    head = 2.0 * 4096 * 50176                                   # 0.41 GB
    assert 0.45 < experts / total < 0.47 and 0.32 < state / total < 0.34
    assert 0.027 < head / total < 0.029
    # 64 tokens of 10 choices in 72 reach every held expert
    assert family.experts_touched_share(m, 64) == pytest.approx(1.0, abs=1e-3)
    assert family.ssm_state_bytes(m, {"occupancy_samples": [32, 64]}) == \
        pytest.approx(state * 0.75)
    assert family.attention_kv_bytes(m, {}) == 0.0
    # the grouped kernel's two calls a layer: gate and up, then down
    up = family.grouped_expert_cost(m, 768, 10 * 1024.0)
    down = family.grouped_expert_cost(m, 4096, 10 * 1024.0)
    assert up["flops"] == 2 * down["flops"] == 2.0 * 5120 * 4096 * 768 * 2
    assert family.grouped_expert_cost(m, 1536, 10.0) is None


STATE_OPS = (
    '%ssm_state_step.3 = (f32[64,128,64]{2,1,0}, f32[9,64,128,64,128]'
    '{4,3,2,1,0}) custom-call(s32[1] %l, f32[9,64,128,64,128] %state), '
    'custom_call_target="tpu_custom_call"',
    "%fusion.5 = bf16[9,64,3,8448]{1,3,2,0} fusion(bf16[9,64,3,8448] "
    "%tail, bf16[64,1,8448] %xbc), kind=kLoop")
MIXER_OPS = (
    "%fusion.6 = f32[64,1,16768]{2,1,0} fusion(bf16[64,1,4096] %u, "
    "bf16[5,4096,16768] %in_proj), kind=kOutput",
    "%fusion.8 = f32[2,128,128,128]{3,2,1,0} fusion(f32[2,128,128] %cs)",
    "%fusion.14 = bf16[2,1024,128,64]{3,2,1,0} fusion(f32[2,1024,8448] %c)")
EXPERT_OPS = (
    "%fusion.20 = f32[36,64,768]{2,1,0} fusion(bf16[64,4096] %h, "
    "bf16[5,36,4096,768] %wi_up), kind=kOutput",
    "%fusion.21 = f32[64,4096]{1,0} fusion(bf16[36,64,768] %act, "
    "bf16[5,36,768,4096] %wo_e), kind=kOutput",
    "%fusion.22 = f32[64,72]{1,0} fusion(f32[64,4096] %h, "
    "f32[5,4096,72] %router), kind=kOutput")
OTHER_OPS = (
    "%fusion.9 = f32[64,50176]{1,0} fusion(bf16[64,4096] %x, "
    "bf16[50176,4096] %embedding), kind=kOutput",
    "%fusion.10 = bf16[64,1,6144]{2,1,0} fusion(bf16[64,1,4096] %u, "
    "bf16[1,4096,6144] %wqkv), kind=kOutput",
    "%fusion.11 = bf16[64,1536]{1,0} fusion(bf16[64,4096] %h, "
    "bf16[5,4096,1536] %ws_up), kind=kOutput",
    "%fusion.7 = f32[64,4096]{1,0} fusion(bf16[64,8192] %y, "
    "bf16[5,8192,4096] %out_proj), kind=kOutput",
    '%paged_decode_attn.3 = bf16[64,32,128]{2,1,0} custom-call(s32[1] %l, '
    'bf16[1,1280,128,8,128] %k), custom_call_target="tpu_custom_call"')


def test_the_mixers_and_the_experts_operations_are_told_by_their_shapes():
    m = cell_config()
    ssm_op, is_expert = family.ssm_op(m), family.expert_ffn_op(m)
    assert all(ssm_op["state"](n) and ssm_op["mixer"](n) for n in STATE_OPS)
    assert all(ssm_op["mixer"](n) and not ssm_op["state"](n)
               for n in MIXER_OPS)
    assert all(is_expert(n) for n in EXPERT_OPS)
    for n in STATE_OPS + MIXER_OPS + OTHER_OPS:
        assert not is_expert(n)
    for n in EXPERT_OPS + OTHER_OPS:
        assert not ssm_op["mixer"](n) and not ssm_op["state"](n)


# -- the two new readers, on a synthetic trace --------------------------------

class _Trace:
    """Eight runs of one decode program of 8 steps, 200 ms each: 64 ms
    under ``ssm_step``, 16 under ``ssm_mixer`` outside it, 100 under
    ``moe_experts``, 8 under ``lm_head``, 12 under no name."""
    devices = [{}]

    def __init__(self, unnamed=12.0):
        ops = (("%s.1 = f32[9,64,128,64,128]{4,3,2,1,0} custom-call()", 64.0),
               ("%m.1 = f32[64,1,16768]{2,1,0} fusion()", 16.0),
               ("%e.1 = f32[36,64,768]{2,1,0} fusion()", 100.0),
               ("%h.1 = f32[64,50176]{1,0} fusion()", 8.0),
               ("%u.1 = f32[64,4096]{1,0} fusion()", unnamed))
        self.modules, self.ops = [], []
        for run in range(8):
            t = run * 0.5
            self.modules.append(("jit_paged_decode_c8_w16(7)", t,
                                 t + sum(ms for _, ms in ops) * 1e-3))
            for name, ms in ops:
                self.ops.append((name, t, t + ms * 1e-3))
                t += ms * 1e-3
        self.devices = [{"modules": self.modules, "ops": self.ops}]

    def module_time(self, match, whole=False):
        runs = [e - s for n, s, e in self.modules if match(n)]
        return sum(runs), len(runs)


MAPS = [{"program": "jit_paged_decode_c8_w16", "scopes": {
    "s.1": ["f32[9,64,128,64,128]", "ssm_step"],
    "m.1": ["f32[64,1,16768]", "ssm_mixer"],
    "e.1": ["f32[36,64,768]", "moe_experts"],
    "h.1": ["f32[64,50176]", "lm_head"],
    "u.1": ["f32[64,4096]", ""]}}]


def _run(trace, config=None):
    return type("Run", (), {
        "trace": trace, "config": config or cell_config(),
        "device": {"kind": "TPU v5 lite", "platform": "tpu"},
        "counters": {"occupancy_samples": [64] * 5,
                     "live_kv_tokens_mean": 64 * 1200.0}})


def test_the_two_readers_read_the_decode_programs_by_scope(monkeypatch):
    monkeypatch.setattr(program_scopes, "scope_maps", lambda: MAPS)
    program_scopes.summary.cache_clear()
    run = _run(_Trace())
    # the update alone, not the mixer's projections round it
    assert harness.load_reader("ssm_step_share")(run) == pytest.approx(32.0)
    assert harness.load_reader("lm_head_share")(run) == pytest.approx(4.0)
    # another family's: no recurrent layer, an untied head
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "olmoe-1b-7b-0125-d10.json")) as f:
        other = _run(run.trace, json.load(f))
    assert harness.load_reader("ssm_step_share")(other) is None
    assert harness.load_reader("lm_head_share")(other) is None
    # over a tenth of the decode runs' time unnamed: the maps are another
    # tree's, and no share is given
    program_scopes.summary.cache_clear()
    holed = _run(_Trace(unnamed=40.0))
    for name in READERS:
        assert harness.load_reader(name)(holed) is None
    # no map (the parent's program, or a CPU rehearsal): nothing, no error
    monkeypatch.setattr(program_scopes, "scope_maps", lambda: None)
    program_scopes.summary.cache_clear()
    for name in READERS:
        assert harness.load_reader(name)(_run(_Trace())) is None
        assert harness.load_reader(name)(_run(None)) is None
    program_scopes.summary.cache_clear()


# -- the entries, by name -----------------------------------------------------

def test_the_cells_entries_keep_the_contract(bench):
    """Every clause of ``test_benchmark_json_keeps_the_contract`` for the
    entries of this cell: one configuration, one cell and its metrics,
    each found by name with the cell under ``workloads``."""
    cell = bench_pins.cell_entry(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG_NAME, "assist-backlog-context", 1)
    assert sum(c["chips"] == 4 for c in bench["workloads"]) == 1
    entry = bench_pins.config_entry(bench, cell["config"])
    config = cell_config()
    bench_pins.check_reduced(entry, config, PUBLISHED)
    assert sorted(entry["reduced"]) == sorted(REDUCED)
    assert config["name"] == entry["name"]
    assert set(bench_pins.reported(bench, CELL, "end_to_end")) == {
        "serve_tokens_per_s", "setup_s"}
    moved = bench_pins.entry(bench["end_to_end"], "serve_tokens_per_s")
    assert CELL in moved["workloads"] and moved["bound"] == 0.045
    mine = bench_pins.reports(bench, CELL, (*TWINS, *OWN),
                              moves="serve_tokens_per_s")
    assert len(bench["per_layer"]) <= 128 and len(bench["workloads"]) <= 24
    assert "lm_head_share" not in bench_pins.reported(bench, CELL)
    # a recurrent state has no prefix to share
    assert "prefix_hit_share" not in bench_pins.reported(bench, CELL)
    for stem, m in mine.items():
        if stem in OWN:
            assert (m["source"], m["better"], m["unit"], m["layer"]) == (
                "device_trace", "lower", "%", OWN[stem])
            assert m["workloads"][0] == CELL
        else:                   # one entry, shared with a cell before
            assert TWINS[stem] in m["workloads"]
    with open(os.path.join(ROOT, "BENCHMARK.json"), "rb") as f:
        assert len(f.read()) <= 64 * 1024


def test_the_traffic_fills_every_slot_at_the_sixteen_page_table():
    bench, cell, config, traffic = harness.load_cell(CELL)
    lengths = {k: (traffic[k]["min"], traffic[k]["max"])
               for k in ("doc_tokens", "question_tokens", "answer_tokens")}
    assert lengths == {"doc_tokens": (512, 768),
                       "question_tokens": (64, 256),
                       "answer_tokens": (320, 640)}
    assert sum(hi for _, hi in lengths.values()) == 1664 < config[
        "system"]["max_len"]
    assert (traffic["generator"], traffic["runner"]) == (
        "doc_backlog", "serve_backlog")
    assert (traffic["askings"], traffic["docs_per_cycle"],
            traffic["wave_docs"], traffic["max_waiting"], traffic["ramp_s"],
            traffic["trace_s"]) == (4, 48, 16, 3, 45, 6)
    assert traffic["prefill_limits"] == {"max_group": 2,
                                         "max_score_elements": 8388608}
    assert 0 < len(traffic["why"])
    from benchmark.generators import doc_backlog

    def lengths_of(seed):
        b = doc_backlog.Backlog(traffic, config["vocab_size"], seed)
        return [(len(r.prompt), r.max_new_tokens) for r in b.first_cycle()]

    first, other = lengths_of(3), lengths_of(2 ** 31 + 17)
    assert len(first) == len(other) == 192 and first != other
    # every prompt in the 1,024 bucket alone
    assert all(576 <= p <= 1024 for p, _ in first + other)
    assert all(896 <= p + n <= 1664 for p, n in first + other)
    assert np.mean([n for _, n in first]) == pytest.approx(480, abs=2)
    # the ids come from the vocabulary's slice
    b = doc_backlog.Backlog(traffic, config["vocab_size"], 5)
    assert max(int(r.prompt.max()) for r in b.first_cycle()) < 50176
    # the warm-up's grid: the 1,024 bucket at eight pages, alone and in
    # pairs (2 x 1024 x 8 x 128 = 2M score elements of the 8M allowed);
    # the longest reservation alive sets the decode table, and with 64
    # slots one of 9 pages or more is always alive: 16
    system = config["system"]
    shapes = [(np.ones(p, np.int32), n) for p, n in first]
    prefill, decode = serving.warm_cells(shapes, system,
                                         traffic["prefill_limits"])
    assert {(1, 1024, 8), (2, 1024, 8)} <= prefill
    assert {t for _, t, _ in prefill} <= {16, 32, 64, 128, 256, 512, 1024}
    assert 16 in decode and decode <= {8, 16}
    assert sum(-(-(p + n) // 128) + 1 > 8 for p, n in first) >= 185
    # 14 pages a slot and the spare: no reservation waits for a page
    assert system["num_pages"] >= system["max_batch"] * 14 + 256


# -- each departure alone fails the comparison that decides ``correct`` ------

sys.path.insert(0, os.path.join(ROOT, "tests"))


@pytest.fixture(scope="module")
def served():
    """Four prompts through the toy engine, one after another as
    ``serving.prepare_engine`` serves its reference check (of one length:
    the reference then compiles once a departure, not once a prompt)."""
    import test_granite_moe_hybrid as toy

    from ray_tpu.serve.paged_llm import PagedLLMEngine

    from ray_tpu.models import granite_moe_hybrid

    cfg = toy.tiny_config()
    # the routed experts' output at twice the committed gain: the
    # committed one (1.25) is what a bf16 program's tipped choices allow
    # at the published widths; this engine is float32 and tips nothing,
    # and at a sixty-fourth of the width the routed part is otherwise too
    # small a share for ``gating`` to pass the limit
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(granite_moe_hybrid, "_ROUTED_OUT_GAIN", 2.0)
        params = toy.make_params(cfg)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, 128, n, dtype=np.int32)
               for n in (48, 48, 48, 48)]
    eng = PagedLLMEngine(cfg=cfg, params=params, max_batch=2, max_len=128,
                         page_size=8, num_pages=40)
    eng.start()
    out = [(p, serving.collect(eng, eng.submit(p, max_new_tokens=40)))
           for p in prompts]
    eng.stop()
    return toy.CONFIG, params, out


@pytest.mark.parametrize("departure", [
    None, {"attention_scale": "rsqrt_head_dim"}, {"residual": 1.0},
    {"gating": "softmax_all"}, {"experts": "reglu"},
    {"gate_norm": "before"}, {"groups": 8}, {"rotary": "rope"},
    {"embedding": 1.0}],
    ids=["published", "attention_scale", "residual", "gating", "experts",
         "gate_norm", "groups", "rotary", "embedding"])
def test_each_departure_alone_reads_not_correct(served, departure):
    config, params, out = served

    def logits(*args):
        return family.logits(*args, **(departure or {}))

    gap = max(reference.token_gap(logits, config, params, prompt, tokens)[0]
              for prompt, tokens in out)
    # 160 tokens (the cell's own check teacher-forces 64, at sixty-four
    # times the width): the published reading within the limit, each
    # departure past it; the thinnest is ``gating``, 0.19 here (the
    # routed part is a third of a branch that is a third of its stream)
    if departure is None:
        assert gap <= serving.TOKEN_GAP_TOL
    else:
        assert gap > serving.TOKEN_GAP_TOL


# -- the cell's runner, rehearsed at toy size --------------------------------

TOY_GEN = {
    "generator": "doc_backlog", "runner": "serve_backlog",
    "doc_tokens": {"dist": "uniform", "min": 8, "max": 14},
    "question_tokens": {"dist": "uniform", "min": 30, "max": 46},
    "answer_tokens": {"dist": "uniform", "min": 20, "max": 40},
    "askings": 4, "docs_per_cycle": 4, "wave_docs": 2, "max_waiting": 2,
    "ramp_s": 0.5, "trace_s": 3, "prefill_limits": bench_toy.LIMITS}
DRIVER = '''
import json, sys
sys.path.insert(0, ".")
from benchmark import harness, program_spans
rc = harness.main(["--workload", "toy-assist-gen", "--seed", sys.argv[1],
                   "--seconds", "4", "--trace", "1", "--rehearse"])
run = type("Run", (), {"trace": None, "counters": {},
                       "config": harness.load_cell("toy-assist-gen")[2]})
values = {name: harness.load_reader(name)(run) for name in json.loads(
    sys.argv[2])}
spans = program_spans.engine_spans() or []
attrs = {}
for s in spans:
    if s["name"] in ("engine.dispatch_prefill", "engine.dispatch_decode"):
        for k in ("state_installs", "state_slots", "state_bytes", "group"):
            if k in s["attrs"]:
                attrs.setdefault(s["name"] + "." + k, []).append(
                    s["attrs"][k])
print("inside " + json.dumps({"rc": rc, "values": values, "attrs": attrs}))
'''


def make_toy_assist(tmp: str) -> str:
    """The toy copy with the CPU tests' toy Granite configuration in
    bf16, a toy mix of ``assist-backlog-context``'s shape (every prompt
    in ONE bucket, 38-60 tokens in the 64 one, answers half to two thirds
    of it; the shared context is under a page, so the warm-up's grid is
    two prefill programs and the test stays inside its seconds) and their
    cell, added as files and entries; the cell reports what
    ``serve-assist-gen`` reports."""
    import test_granite_moe_hybrid as toy

    root = bench_toy.make_toy(tmp)
    config = dict(toy.CONFIG, name="toy-granite-serve",
                  family="granite_moe_hybrid",
                  layer_types=["mamba", "attention", "mamba"],
                  num_hidden_layers=3,
                  source="none: a toy for the CPU tests", reduced=[],
                  torch_dtype="bfloat16", system={
                      "max_batch": 4, "max_len": 128, "page_size": 16,
                      "num_pages": 36, "kv_dtype": "bf16",
                      "prefix_cache": False,
                      "reference_check": {"prompt_tokens": 60,
                                          "shared_tokens": 0,
                                          "new_tokens": 6}})
    for name, data in (("configs/toy-granite-serve", config),
                       ("traffic/toy-assist", TOY_GEN)):
        with open(os.path.join(root, "benchmark", name + ".json"), "w") as f:
            json.dump(data, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "toy-granite-serve", "source": "none", "reduced": [],
        "why": "toy", "file": "benchmark/configs/toy-granite-serve.json"})
    bench["workloads"].append({
        "name": "toy-assist-gen", "config": "toy-granite-serve",
        "traffic": "toy-assist", "chips": 1, "why": "toy"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("toy-assist-gen")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def test_toy_granite_rehearses_the_cells_runner(tmp_path):
    """A three-layer stack (a mixer, attention, a mixer: the toy's plan
    less a layer, for the suite's clock) through ``serve_backlog`` on the
    CPU, in bf16 as the cell serves it, prefix cache off as the cell has
    it: the float32 reference calls the engine's tokens correct with
    every slot retiring and refilling through the run, the dispatch spans
    carry the counts of a state that two of the three layers keep, and
    the chunks' expert statistics are means over all THREE (the runs that
    keep state route too)."""
    root = make_toy_assist(str(tmp_path))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""),
               JAX_COMPILATION_CACHE_DIR=os.path.join(root, ".jax_cache"))
    names = ["experts_touched_mean", "routed_here_share",
             "expert_load_max_over_mean", "decode_active_share",
             "ssm_step_share", "lm_head_share", "ssm_mixer_share",
             "ssm_state_roofline", "prefill_scan_share",
             "prefill_expert_share", "paged_attn_roofline",
             "expert_ffn_share", "unscoped_share"]
    r = subprocess.run(
        [sys.executable, "-c", DRIVER, str(2 ** 31 + 5), json.dumps(names)],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    lines = r.stdout.strip().splitlines()
    rehearsal = json.loads(lines[-2].split(" ", 1)[1])
    got = json.loads(lines[-1].split(" ", 1)[1])
    assert got["rc"] == 0
    assert rehearsal["correct"] is True and rehearsal["failed"] == 0
    assert rehearsal["attempted"] > 0
    # a rehearsal prints counters only, and this cell has no prefix to hit
    assert set(rehearsal["metrics"]) == {"compiles_in_window"}
    assert rehearsal["metrics"]["compiles_in_window"]["value"] <= 1.0
    values = got["values"]
    assert values["decode_active_share"] > 50.0
    assert all(values[n] is None for n in names[4:])    # no device trace
    # the chunks' means over the THREE layers: up to 4 live tokens of 4
    # choices over 16 experts, 8 of them held
    assert 0.0 < values["experts_touched_mean"] <= 8.0
    assert 0.0 < values["routed_here_share"] < 100.0
    assert values["expert_load_max_over_mean"] >= 1.0
    attrs = got["attrs"]
    # float32 S [8, 8, 16] and a bf16 tail [3, 96] in each of 2 layers
    slot_bytes = 2 * (4 * 8 * 8 * 16 + 2 * 3 * 96)
    assert attrs["engine.dispatch_prefill.state_installs"] == \
        attrs["engine.dispatch_prefill.group"]
    assert all(b == 2 * n * slot_bytes for b, n in zip(
        attrs["engine.dispatch_decode.state_bytes"],
        attrs["engine.dispatch_decode.state_slots"]))
