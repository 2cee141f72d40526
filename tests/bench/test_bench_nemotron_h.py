"""The ``nemotron_h`` model family of the benchmark (PR 43): its file passes
the family contract, the configuration is the published one but for its
cuts (the published keys written HERE, not read from a catalog outside the
repo), its counts are pinned at the published widths, its predicates tell
the mixer's, the experts' and the attention's operations apart on a
synthetic trace that holds shapes the three share, the new reader
``prefill_expert_share`` and the older ones read that trace, the cell's
entries keep the contract, its traffic keeps every prompt in the 512
bucket and the run at the 16-page table, each named departure of the
reference alone reads not correct at the toy size, and a toy configuration
rehearses ``serve-reason-gen``'s runner on the CPU."""

import ast
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_pins  # noqa: E402
import bench_toy  # noqa: E402

from benchmark import harness, inside, reference, serving, systems  # noqa: E402
from benchmark.families import nemotron_h as family  # noqa: E402
from benchmark.trace import Trace  # noqa: E402

ROOT = bench_toy.REPO
CELL, SUFFIX = "serve-reason-gen", ""
CONFIG_NAME = "nemotron-3-nano-30b-a3b-ep2-d9"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
# the older readers' entries, each with a cell it shares the entry with
TWINS = {"decode_program_step_ms": "serve-instruct-gen",
         "decode_roofline": "serve-instruct-gen",
         "prefill_program_share": "serve-instruct-gen",
         "decode_active_share": "serve-instruct-gen",
         "decode_delivered_share": "serve-instruct-gen",
         "decode_overrun_share": "serve-instruct-gen",
         "prefill_fill_share": "serve-instruct-gen",
         "device_idle_share": "serve-instruct-gen",
         "peak_hbm_gb": "serve-instruct-gen",
         "engine_host_share": "serve-instruct-gen",
         "paged_attn_roofline": "serve-instruct-gen",
         "expert_ffn_share": "serve-note-gen",
         "experts_touched_mean": "serve-note-gen",
         "expert_load_max_over_mean": "serve-note-gen",
         "routed_here_share": "serve-note-gen",
         "ssm_mixer_share": "serve-instruct-gen",
         "ssm_state_roofline": "serve-instruct-gen",
         "prefill_scan_share": "serve-instruct-gen"}
OWN = "prefill_expert_share"
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
# https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/
# main/config.json, the keys that say something about the model's shape
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern": PATTERN, "intermediate_size": 1856,
    "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
    "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2",
    "model_type": "nemotron_h", "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
    "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1,
    "time_step_min": 0.001, "topk_group": 1, "use_bias": False,
    "use_conv_bias": True, "use_mamba_kernels": True, "vocab_size": 131072}
REDUCED = ["num_hidden_layers", "hybrid_override_pattern",
           "n_routed_experts", "vocab_size"]


def cell_config() -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs",
                           CONFIG_NAME + ".json")) as f:
        return json.load(f)


# -- the family's file -------------------------------------------------------

def test_the_family_passes_the_api_check_and_keeps_off_the_program():
    assert systems.family({"family": "nemotron_h"}) is family
    path = os.path.join(ROOT, "benchmark", "families", "nemotron_h.py")
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


def test_the_configuration_is_the_published_one_but_for_its_cuts():
    import jax

    config = cell_config()
    assert config["source"] == (
        "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/"
        "blob/main/config.json")
    differs = [k for k, v in PUBLISHED.items() if config.get(k, "") != v]
    assert sorted(differs) == sorted(REDUCED) and config["reduced"] == REDUCED
    assert config["reduced_from"] == {k: PUBLISHED[k] for k in REDUCED}
    # the pattern's first nine letters: 4 mixers, 4 expert layers and 1
    # attention layer, a whole period MEMEM*E and two layers more
    assert config["hybrid_override_pattern"] == PATTERN[:9] == "MEMEM*EME"
    assert config["num_hidden_layers"] == 9
    assert family.layer_counts(config) == (4, 4, 1)
    assert config["expert_share"] == {"chips": 2, "index": 0,
                                      "num_experts_total": 128}
    # the floors: 8 experts a layer, an eighth of the vocabulary
    assert config["n_routed_experts"] == 64 >= 8
    assert config["vocab_size"] == 65536 >= PUBLISHED["vocab_size"] // 8
    assert set(config["assumed"]) >= {"rotary", "torch_dtype", "state_dtype",
                                      "router", "experts", "init"}
    for key in ("reduced_why", "deployment"):
        assert len(config[key]) > 200
    assert config["system"] == {
        "max_batch": 128, "max_len": 2048, "page_size": 128,
        "num_pages": 2304, "kv_dtype": "bf16", "prefix_cache": False,
        "reference_check": {"prompt_tokens": 448, "shared_tokens": 0,
                            "new_tokens": 32}}
    cfg = family.model_config(config)
    assert (cfg.n_experts, cfg.n_experts_held, cfg.first_expert,
            cfg.top_k) == (128, 64, 0, 6)
    assert (cfg.d_ssm, cfg.conv_dim, cfg.pattern) == (4096, 6144,
                                                      "MEMEM*EME")
    shapes = jax.eval_shape(lambda: family.init_params(
        cfg, jax.random.key(0)))
    held = sum(a.size for a in jax.tree.leaves(shapes))
    assert held == family.total_params(config)
    with pytest.raises(ValueError, match="published Nemotron-H layers"):
        family.model_config(dict(config, mlp_hidden_act="silu"))


def test_counts_at_the_published_widths():
    m = cell_config()
    assert family.mixer_params(m) == 38_744_896      # its norm among them
    assert family.attention_params(m) == 23_399_040
    assert family.expert_params(m) == 9_977_856
    assert family.shared_expert_params(m) == 19_955_712
    assert family.router_params(m) == 344_192
    assert 3.16e9 < family.total_params(m) < 3.17e9
    whole = dict(PUBLISHED, expert_share=None)
    assert 31.5e9 < family.total_params(whole) < 31.7e9     # "31.6B"
    assert family.state_bytes_per_slot_layer(m) == 4 * 64 * 64 * 128 \
        + 2 * 3 * 6144
    assert family.kv_bytes_per_token_layer(m) * 128 == 131_072


def test_decode_step_bytes_at_the_cells_sizes():
    m = cell_config()
    full = {"occupancy_samples": [128] * 4,
            "live_kv_tokens_mean": 128 * 1280.0}
    total = family.decode_step_bytes(m, full)
    state, kv = family.ssm_state_bytes(m, full), family.attention_kv_bytes(
        m, full)
    assert state == pytest.approx(2 * 4 * 128 * 2_134_016)
    assert kv == pytest.approx(128 * 1280 * 1024)
    assert 8.2e9 < total < 8.45e9
    experts = 2.0 * 4 * 64 * family.expert_params(m)
    assert 0.60 < experts / total < 0.63 and 0.25 < state / total < 0.28
    # 128 tokens of 6 choices in 128 reach every held expert but 0.2%
    assert family.experts_touched_share(m, 128) == pytest.approx(0.9978,
                                                                 abs=1e-3)
    assert family.ssm_state_bytes(m, {"occupancy_samples": [64, 128]}) == \
        pytest.approx(state * 0.75)
    assert family.attention_kv_bytes(m, {}) == 0.0


# -- the predicates and the readers, on a synthetic trace --------------------

DECODE = "jit_paged_decode_c16_w16(123)"
PREFILL = "jit_paged_prefill_w4(456)"
STATE_OPS = (
    '%ssm_state_step.3 = (f32[128,64,64]{2,1,0}, f32[4,128,64,64,128]'
    '{4,3,2,1,0}) custom-call(s32[1] %l, f32[4,128,64,64,128] %state), '
    'custom_call_target="tpu_custom_call"',
    "%fusion.5 = bf16[4,128,3,6144]{1,3,2,0} fusion(bf16[4,128,3,6144] "
    "%tail, bf16[128,1,6144] %xbc), kind=kLoop")
MIXER_OPS = (
    "%fusion.6 = f32[128,1,10304]{2,1,0} fusion(bf16[128,1,2688] %u, "
    "bf16[1,2688,10304] %in_proj), kind=kOutput",
    "%fusion.8 = f32[2,64,128,128]{3,2,1,0} fusion(f32[2,128,64] %cs)",
    "%fusion.14 = bf16[2,512,64,64]{3,2,1,0} fusion(f32[2,512,6144] %conv)")
EXPERT_OPS = (
    "%fusion.20 = f32[64,128,1856]{2,1,0} fusion(bf16[128,2688] %h, "
    "bf16[1,64,2688,1856] %wi_up), kind=kOutput",
    "%fusion.21 = f32[128,2688]{1,0} fusion(bf16[64,128,1856] %act, "
    "bf16[1,64,1856,2688] %wo_e), kind=kOutput",
    "%fusion.22 = f32[128,128]{1,0} fusion(f32[128,2688] %h, "
    "f32[1,2688,128] %router), kind=kOutput")
# what shapes alone cannot tell apart: the mixer's out_proj and
# attention's wo, both [4096, 2688] behind a row 4096 wide; and what is
# nobody's: the shared expert, the head, the attention kernel, a loop
SHARED_SHAPES = (
    "%fusion.7 = f32[128,2688]{1,0} fusion(bf16[128,4096] %y, "
    "bf16[1,4096,2688] %out_proj), kind=kOutput",
    "%fusion.30 = bf16[128,1,2688]{2,1,0} fusion(bf16[128,4096] %attn, "
    "bf16[1,4096,2688] %wo), kind=kOutput")
OTHER_OPS = (
    "%fusion.9 = f32[128,65536]{1,0} fusion(bf16[128,2688] %x, "
    "bf16[2688,65536] %head), kind=kOutput",
    "%fusion.10 = bf16[128,1,4608]{2,1,0} fusion(bf16[128,1,2688] %u, "
    "bf16[1,2688,4608] %wqkv), kind=kOutput",
    "%fusion.11 = bf16[128,3712]{1,0} fusion(bf16[128,2688] %h, "
    "bf16[1,2688,3712] %ws_up), kind=kOutput",
    '%paged_decode_attn.3 = bf16[128,32,128]{2,1,0} custom-call(s32[1] %l, '
    'bf16[1,2304,128,2,128] %k), custom_call_target="tpu_custom_call"',
    "%while.4 = (s32[], f32[4,128,64,64,128]) while(%tuple.3)")


def synthetic_trace(runs: int = 6) -> Trace:
    """``runs`` decode runs of 16 steps in 208 ms (13 ms a step), each
    step: four state updates of 0.8 ms, four input projections of 0.2 ms,
    four expert layers of two 0.9 ms matmuls, one out_proj and one wo of
    0.1 ms (neither counted), the attention kernel 0.2 ms, the head 0.5
    ms; and ``runs`` prefill runs of 50 ms with 30 ms in the experts'
    operations and 8 ms in the scan's."""
    modules, ops = [], []
    for i in range(runs):
        t = 0.3 * i
        modules.append((DECODE, t, t + 0.208))
        for j in range(16):
            at = t + 0.013 * j
            for k in range(4):
                ops.append((STATE_OPS[0], at, at + 0.0008))
                ops.append((MIXER_OPS[0], at + 0.0008, at + 0.001))
                ops.append((EXPERT_OPS[0], at + 0.001, at + 0.0019))
                ops.append((EXPERT_OPS[1], at + 0.0019, at + 0.0028))
                at += 0.0028
            ops.append((SHARED_SHAPES[0], at, at + 0.0001))
            ops.append((SHARED_SHAPES[1], at + 0.0001, at + 0.0002))
            ops.append((OTHER_OPS[3], at + 0.0002, at + 0.0004))
            ops.append((OTHER_OPS[0], at + 0.0004, at + 0.0009))
        ops.append((OTHER_OPS[4], t, t + 0.208))      # the loop itself
        p = t + 0.22
        modules.append((PREFILL, p, p + 0.050))
        ops.append((EXPERT_OPS[0], p, p + 0.018))
        ops.append((EXPERT_OPS[1], p + 0.018, p + 0.030))
        ops.append((MIXER_OPS[1], p + 0.030, p + 0.034))
        ops.append((MIXER_OPS[2], p + 0.034, p + 0.038))
        ops.append((OTHER_OPS[2], p + 0.038, p + 0.050))
    return Trace([{"modules": modules, "ops": ops, "async_ops": []}], [],
                 extent_s=0.3 * runs)


def test_the_three_kinds_operations_are_told_by_what_differs():
    m = cell_config()
    ssm_op, is_expert = family.ssm_op(m), family.expert_ffn_op(m)
    assert all(ssm_op["state"](n) and ssm_op["mixer"](n) for n in STATE_OPS)
    assert all(ssm_op["mixer"](n) and not ssm_op["state"](n)
               for n in MIXER_OPS)
    assert all(is_expert(n) for n in EXPERT_OPS)
    assert is_expert("%ragged-dot.1 = f32[6144,1856]{1,0} ragged-dot(%a)")
    # no operation is two kinds', and what two kinds share is neither's
    for n in STATE_OPS + MIXER_OPS:
        assert not is_expert(n)
    for n in EXPERT_OPS + SHARED_SHAPES + OTHER_OPS[:4]:
        assert not ssm_op["mixer"](n) and not ssm_op["state"](n)
    for n in SHARED_SHAPES + OTHER_OPS[:4]:
        assert not is_expert(n)


def test_the_readers_on_a_synthetic_trace():
    m = cell_config()
    counters = {"occupancy_samples": [128] * 5,
                "live_kv_tokens_mean": 128 * 1280.0}
    run = type("Run", (), {"trace": synthetic_trace(), "config": m,
                           "counters": counters,
                           "device": {"kind": "TPU v5 lite"}})
    assert inside.decode_program_step_ms(run.trace) == pytest.approx(13.0)
    # of a step's 13 ms: 4 x (0.8 + 0.2) in the mixer's operations, 4 x
    # 1.8 in the experts'; the out_proj and wo matmuls in neither
    assert harness.load_reader("ssm_mixer_share")(run) == \
        pytest.approx(100.0 * 4.0 / 13.0)
    assert harness.load_reader("expert_ffn_share")(run) == \
        pytest.approx(100.0 * 7.2 / 13.0)
    # 2.185 GB of state at 819 GB/s are 2.67 ms against the 3.2 measured
    got = harness.load_reader("ssm_state_roofline")(run)
    assert got == pytest.approx(
        100.0 * family.ssm_state_bytes(m, counters) / 819e9 / 3.2e-3,
        rel=1e-3)
    assert 80.0 < got < 86.0
    # 0.168 GB of keys and values are 0.205 ms against the kernel's 0.2:
    # the synthetic kernel runs at its roofline, and a real one under it
    assert harness.load_reader("paged_attn_roofline")(run) == \
        pytest.approx(100.0 * 128 * 1280 * 1024 / 819e9 / 0.2e-3, rel=1e-3)
    # of a prefill run's 50 ms, 30 in the experts' and 8 in the scan's
    assert harness.load_reader("prefill_expert_share")(run) == \
        pytest.approx(60.0)
    assert harness.load_reader("prefill_scan_share")(run) == \
        pytest.approx(16.0)
    names = ["prefill_expert_share", "prefill_scan_share",
             "ssm_state_roofline", "expert_ffn_share"]
    # too few runs, no trace, another family, and a program with no such
    # operation (the parent's, were it to run the cell): nothing, no error
    run.trace = synthetic_trace(inside.MIN_SAMPLES - 1)
    assert [harness.load_reader(n)(run) for n in names] == [None] * 4
    run.trace = None
    assert [harness.load_reader(n)(run) for n in names] == [None] * 4
    run.trace, run.config = synthetic_trace(), {"family": "llama"}
    assert [harness.load_reader(n)(run) for n in names] == [None] * 4
    bare = synthetic_trace()
    bare.devices[0]["ops"] = [op for op in bare.devices[0]["ops"]
                              if op[0] in OTHER_OPS + SHARED_SHAPES]
    run.trace, run.config = bare, m
    assert harness.load_reader("prefill_expert_share")(run) is None
    assert harness.load_reader("prefill_scan_share")(run) is None
    assert harness.load_reader("ssm_state_roofline")(run) is None
    assert not harness.load_reader("expert_ffn_share")(run)


# -- the entries, by name -----------------------------------------------------

def test_the_cells_entries_keep_the_contract(bench):
    """Every clause of ``test_benchmark_json_keeps_the_contract`` for the
    entries of this cell: one configuration, one cell and its metrics,
    each found by name with the cell under ``workloads``, and the lists
    within their limits with room to spare."""
    cell = bench_pins.cell_entry(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG_NAME, "reason-backlog-trace", 1)
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
    mine = bench_pins.reports(bench, CELL, (*TWINS, OWN),
                              moves="serve_tokens_per_s")
    assert len(bench["per_layer"]) < 64 and len(bench["workloads"]) <= 24
    # a recurrent state has no prefix to share
    assert "prefix_hit_share" not in bench_pins.reported(bench, CELL)
    for stem, m in mine.items():
        if stem == OWN:
            assert (m["source"], m["better"], m["unit"], m["layer"]) == (
                "device_trace", "lower", "%", "routed experts")
        else:                   # one entry, shared with a cell before
            assert TWINS[stem] in m["workloads"]
    with open(os.path.join(ROOT, "BENCHMARK.json"), "rb") as f:
        assert len(f.read()) <= 64 * 1024


def test_the_traffic_fills_every_slot_at_the_sixteen_page_table():
    bench, cell, config, traffic = harness.load_cell(CELL)
    lengths = {k: (traffic[k]["min"], traffic[k]["max"])
               for k in ("doc_tokens", "question_tokens", "answer_tokens")}
    assert lengths == {"doc_tokens": (192, 320),
                       "question_tokens": (64, 192),
                       "answer_tokens": (640, 1152)}
    assert sum(hi for _, hi in lengths.values()) == 1664 < config[
        "system"]["max_len"]
    assert (traffic["generator"], traffic["runner"]) == (
        "doc_backlog", "serve_backlog")
    assert (traffic["askings"], traffic["docs_per_cycle"],
            traffic["wave_docs"], traffic["max_waiting"], traffic["ramp_s"],
            traffic["trace_s"]) == (4, 48, 16, 6, 45, 6)
    assert traffic["prefill_limits"] == {"max_group": 2,
                                         "max_score_elements": 8388608}
    assert 0 < len(traffic["why"])
    from benchmark.generators import doc_backlog

    def lengths_of(seed):
        b = doc_backlog.Backlog(traffic, config["vocab_size"], seed)
        return [(len(r.prompt), r.max_new_tokens) for r in b.first_cycle()]

    first, other = lengths_of(3), lengths_of(2 ** 31 + 17)
    assert len(first) == len(other) == 192 and first != other
    assert sorted(n for _, n in first) == sorted(n for _, n in other)
    # every prompt in the 512 bucket alone, answers two to three times it
    assert all(256 <= p <= 512 for p, _ in first + other)
    assert all(896 <= p + n <= 1664 for p, n in first + other)
    assert np.mean([n for _, n in first]) == pytest.approx(896, abs=2)
    # the ids come from the vocabulary's slice
    b = doc_backlog.Backlog(traffic, config["vocab_size"], 5)
    assert max(int(r.prompt.max()) for r in b.first_cycle()) < 65536
    # the warm-up's grid: the 512 bucket at four pages, alone and in
    # pairs; the longest reservation alive sets the decode table, and
    # with 128 slots one of 13 pages or more is always alive: 16
    system = config["system"]
    shapes = [(np.ones(p, np.int32), n) for p, n in first]
    prefill, decode = serving.warm_cells(shapes, system,
                                         traffic["prefill_limits"])
    # (the grid also holds suffixes behind shared preambles, which a
    # prefix cache would bring and this plan's engine never sees)
    assert {(1, 512, 4), (2, 512, 4)} <= prefill
    assert {t for _, t, _ in prefill} <= {16, 32, 64, 128, 256, 512}
    assert 16 in decode and decode <= {8, 16}
    assert sum(-(-(p + n) // 128) + 1 > 8 for p, n in first) >= 190
    # 16 pages a slot and the spare: no reservation waits for a page
    assert system["num_pages"] >= system["max_batch"] * 14 + 256


# -- each departure alone fails the comparison that decides ``correct`` ------

sys.path.insert(0, os.path.join(ROOT, "tests"))


@pytest.fixture(scope="module")
def served():
    """Four prompts through the toy engine, one after another as
    ``serving.prepare_engine`` serves its reference check."""
    import test_nemotron_h as toy

    from ray_tpu.serve.paged_llm import PagedLLMEngine

    cfg = family.model_config(toy.CONFIG)
    params = toy.make_params(cfg)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, 128, n, dtype=np.int32)
               for n in (50, 37, 9, 64)]
    eng = PagedLLMEngine(cfg=cfg, params=params, max_batch=2, max_len=128,
                         page_size=8, num_pages=40)
    eng.start()
    out = [(p, serving.collect(eng, eng.submit(p, max_new_tokens=16)))
           for p in prompts]
    eng.stop()
    return toy.CONFIG, params, out


@pytest.mark.parametrize("departure", [
    None, {"rotary": "rope"}, {"scores": "softmax"}, {"bias": "none"},
    {"experts": "swiglu"}, {"scale": 1}, {"gate_norm": "before"},
    {"groups": 1}, {"conv_bias": False}],
    ids=["published", "rotary", "scores", "bias", "experts", "scale",
         "gate_norm", "groups", "conv_bias"])
def test_each_departure_alone_reads_not_correct(served, departure):
    config, params, out = served

    def logits(*args):
        return family.logits(*args, **(departure or {}))

    gap = max(reference.token_gap(logits, config, params, prompt, tokens)[0]
              for prompt, tokens in out)
    # 64 tokens, as many as the cell's own check teacher-forces: the
    # published reading within the limit, each departure past it with
    # half as much again to spare; but the two that touch the ROUTED
    # experts' part alone (which stands at a fifth of a branch's scale,
    # so that a tipped choice moves little: ``models/nemotron_h.py``)
    # past it with less: 0.15 and 0.12 here
    if departure is None:
        assert gap <= serving.TOKEN_GAP_TOL
    elif set(departure) & {"bias", "scale"}:
        assert gap > serving.TOKEN_GAP_TOL
    else:
        assert gap > 1.5 * serving.TOKEN_GAP_TOL


# -- the cell's runner, rehearsed at toy size --------------------------------

TOY_GEN = {
    "generator": "doc_backlog", "runner": "serve_backlog",
    "doc_tokens": {"dist": "uniform", "min": 32, "max": 64},
    "question_tokens": {"dist": "uniform", "min": 4, "max": 24},
    "answer_tokens": {"dist": "uniform", "min": 60, "max": 120},
    "askings": 4, "docs_per_cycle": 4, "wave_docs": 2, "max_waiting": 3,
    "ramp_s": 1, "trace_s": 4, "prefill_limits": bench_toy.LIMITS}
DRIVER = '''
import json, sys
sys.path.insert(0, ".")
from benchmark import harness, program_spans
rc = harness.main(["--workload", "toy-reason-gen", "--seed", sys.argv[1],
                   "--seconds", "5", "--trace", "1", "--rehearse"])
run = type("Run", (), {"trace": None, "counters": {},
                       "config": harness.load_cell("toy-reason-gen")[2]})
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


def make_toy_nano(tmp: str) -> str:
    """The toy copy with the CPU tests' toy Nemotron-H configuration in
    bf16, a toy mix of ``reason-backlog-trace``'s shape (answers two to
    three times the prompt) and their cell, added as files and entries;
    the cell reports what ``serve-reason-gen`` reports."""
    import test_nemotron_h as toy

    root = bench_toy.make_toy(tmp)
    config = dict(toy.CONFIG, name="toy-nano-serve", family="nemotron_h",
                  source="none: a toy for the CPU tests", reduced=[],
                  torch_dtype="bfloat16", chunk_size=16, system={
                      "max_batch": 4, "max_len": 256, "page_size": 16,
                      "num_pages": 68, "kv_dtype": "bf16",
                      "prefix_cache": False,
                      "reference_check": {"prompt_tokens": 90,
                                          "shared_tokens": 0,
                                          "new_tokens": 6}})
    for name, data in (("configs/toy-nano-serve", config),
                       ("traffic/toy-reason", TOY_GEN)):
        with open(os.path.join(root, "benchmark", name + ".json"), "w") as f:
            json.dump(data, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "toy-nano-serve", "source": "none", "reduced": [],
        "why": "toy", "file": "benchmark/configs/toy-nano-serve.json"})
    bench["workloads"].append({
        "name": "toy-reason-gen", "config": "toy-nano-serve",
        "traffic": "toy-reason", "chips": 1, "why": "toy"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("toy-reason-gen")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def test_toy_nano_rehearses_the_cells_runner(tmp_path):
    """The nine-layer stack through ``serve_backlog`` on the CPU, in bf16
    as the cell serves it, prefix cache off as the cell has it: the
    float32 reference calls the engine's tokens correct with every slot
    retiring and refilling all through the run, and the dispatch spans
    carry the counts of a state that four of the nine layers keep."""
    root = make_toy_nano(str(tmp_path))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""),
               JAX_COMPILATION_CACHE_DIR=os.path.join(root, ".jax_cache"))
    names = ["experts_touched_mean", "routed_here_share",
             "expert_load_max_over_mean", "engine_host_share",
             "decode_active_share",
             "ssm_mixer_share", "ssm_state_roofline",
             "prefill_scan_share", "prefill_expert_share",
             "paged_attn_roofline", "expert_ffn_share"]
    r = subprocess.run(
        [sys.executable, "-c", DRIVER, str(2 ** 31 + 5), json.dumps(names)],
        cwd=root, env=env, capture_output=True, text=True, timeout=900)
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
    assert all(values[n] is None for n in names[5:])    # no device trace
    # the chunks' means over the FOUR layers that report them: up to 4
    # live tokens of 3 choices over 8 experts, 4 of them held
    assert 0.0 < values["experts_touched_mean"] <= 4.0
    assert 0.0 < values["routed_here_share"] < 100.0
    assert values["expert_load_max_over_mean"] >= 1.0
    attrs = got["attrs"]
    # float32 S [8, 8, 16] and a bf16 tail [3, 192] in each of 4 layers
    slot_bytes = 4 * (4 * 8 * 8 * 16 + 2 * 3 * 192)
    assert attrs["engine.dispatch_prefill.state_installs"] == \
        attrs["engine.dispatch_prefill.group"]
    assert all(b == 2 * n * slot_bytes for b, n in zip(
        attrs["engine.dispatch_decode.state_bytes"],
        attrs["engine.dispatch_decode.state_slots"]))
