"""The ``dots3_note`` model family of the benchmark (PR 40): its file passes
the family contract, the configuration is the published one but for its
cuts (the published keys written HERE, not read from a catalog outside the
repo), its counts are pinned at the published widths, its four new readers
read synthetic traces and spans (the operations' names are a v5e trace's
own), and a toy configuration of it rehearses ``serve-note-gen``'s runner
on the CPU, in a temporary copy to which the toy is added as new files and
entries: ``correct`` true, and false once the indexer is dropped from the
reference."""

import ast
import json
import os
import re
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_pins  # noqa: E402
import bench_toy  # noqa: E402

from benchmark import harness, inside, program_spans, systems  # noqa: E402
from benchmark.families import dots3_note as family  # noqa: E402
from benchmark.trace import Trace  # noqa: E402

ROOT = bench_toy.REPO
CELL, SUFFIX = "serve-note-gen", ""
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
TWINS = ("decode_program_step_ms", "decode_roofline",
         "prefill_program_share", "prefix_hit_share",
         "device_idle_share", "peak_hbm_gb", "engine_host_share",
         "decode_active_share", "decode_delivered_share",
         "decode_overrun_share", "prefill_fill_share", "expert_ffn_share",
         "experts_touched_mean", "expert_load_max_over_mean",
         "routed_here_share",
         "kv_window_read_share")
OWN = {"latent_attn_share": ("device_trace", "lower"),
       "index_select_share": ("device_trace", "lower"),
       "latent_attn_roofline": ("device_trace", "higher"),
       "kv_selected_share": ("program_span", "lower")}
PERIOD = ["full_attention"] + ["sliding_attention"] * 3
# https://huggingface.co/dots-studio/dots3-note-prev/blob/main/config.json,
# the keys that say something about the language model's shape
PUBLISHED = {
    "apply_mla_qkv_lora_rescale": True, "attention_bias": False,
    "attention_gate_type": "headwise", "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 5120, "index_head_dim": 128,
    "index_n_heads": 64, "index_topk": 2048, "intermediate_size": 13824,
    "kv_lora_rank": 512,
    "layer_types": ["full_attention"] + PERIOD * 11 + ["full_attention"],
    "max_position_embeddings": 524288, "model_type": "dots3_note",
    "moe_intermediate_size": 1536, "moe_layer_freq": 1,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 46, "num_key_value_heads": 128,
    "q_lora_rank": 1024, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 80000000,
    "routed_scaling_factor": 1, "scoring_func": "sigmoid",
    "sliding_window_size": 513, "swa_attention_gate_type": "headwise",
    "swa_kv_lora_rank": 1024, "swa_num_attention_heads": 64,
    "swa_num_key_value_heads": 64, "swa_q_lora_rank": 1024,
    "swa_qk_nope_head_dim": 192, "swa_qk_rope_head_dim": 64,
    "swa_rope_theta": 50000, "swa_v_head_dim": 128,
    "tie_word_embeddings": False, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 152064}
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size",
           "layer_types"]


def cell_config() -> dict:
    with open(os.path.join(
            ROOT, "benchmark/configs/dots3-note-prev-ep8-d5.json")) as f:
        return json.load(f)


# -- the family's file -------------------------------------------------------

def test_the_family_passes_the_api_check_and_keeps_off_the_program():
    assert systems.family({"family": "dots3_note"}) is family
    path = os.path.join(ROOT, "benchmark", "families", "dots3_note.py")
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
    assert config["source"] == ("https://huggingface.co/dots-studio/"
                                "dots3-note-prev/blob/main/config.json")
    differs = [k for k, v in PUBLISHED.items() if config.get(k, "") != v]
    assert sorted(differs) == sorted(REDUCED) and config["reduced"] == REDUCED
    assert {k: config["reduced_from"][k] for k in REDUCED[:3]} == {
        "num_hidden_layers": 46, "n_routed_experts": 256,
        "vocab_size": 152064}
    assert set(config["reduced_from"]) == set(REDUCED)
    # the first five entries: the leading dense layer (full) and one whole
    # period after it (full, sliding x 3)
    assert config["layer_types"] == PUBLISHED["layer_types"][:5] == [
        "full_attention"] + PERIOD
    assert config["expert_share"] == {"chips": 8, "index": 0,
                                      "num_experts_total": 256}
    # the floors: a whole period and four layers after the leading dense
    # one, at least 8 experts a layer, at least an eighth of the vocabulary
    assert config["n_routed_experts"] == 32
    assert config["vocab_size"] * 8 == 152064
    assert {"rescale", "gate", "window", "indexer", "groups", "rope",
            "init", "expert_share"} <= set(config["assumed"])
    assert "8 chips" in config["reduced_why"] and "64" in config["deployment"]
    cfg = family.model_config(config)
    assert (cfg.n_experts, cfg.n_experts_held, cfg.first_expert) == (
        256, 32, 0)
    assert (cfg.n_heads, cfg.kv_rank, cfg.nope_dim, cfg.index_topk) == (
        128, 512, 128, 2048)
    assert (cfg.n_heads_sliding, cfg.kv_rank_sliding, cfg.nope_dim_sliding,
            cfg.window) == (64, 1024, 192, 513)
    assert cfg.mlp_only_layers == (0,) and cfg.rescale is True
    # the program's weights are the family's count, leaf for leaf: 4.09B
    shapes = jax.eval_shape(lambda k: family.init_params(cfg, k),
                            jax.random.key(0))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == \
        family.total_params(config) == 4_087_154_176
    assert shapes["blocks"]["layers2-4"]["router"].shape == (3, 5120, 256)
    assert shapes["blocks"]["layers2-4"]["wi_gate"].shape == (
        3, 32, 5120, 1536)
    assert shapes["blocks"]["layers1"]["w_in"].shape == (
        1, 5120, 1024 + 512 + 64 + 128 + 128 + 64)
    system = config["system"]
    assert system["max_batch"] == 64 and system["max_len"] == 8192
    assert system["num_pages"] == 64 * 41 + 192 and system["page_size"] == 128
    check = system["reference_check"]
    # past index_topk, so that the selection drops keys; the second prompt
    # reuses whole pages of the first
    assert check["prompt_tokens"] > 2048 + 513
    assert check["shared_tokens"] % 128 == 0 and check["shared_tokens"] >= 2048


def test_counts_at_the_published_widths():
    m = cell_config()
    assert family.expert_params(m) == 3 * 5120 * 1536            # 23.59M
    assert family.attention_layer_counts(m) == (2, 3)
    full, sliding = (family.attention_params(m, s) for s in (False, True))
    assert full == pytest.approx(144.05e6, rel=1e-3)
    assert sliding == pytest.approx(90.83e6, rel=1e-3)
    assert family._indexer_params(m) == pytest.approx(9.37e6, rel=1e-3)
    assert family.dense_mlp_params(m) == pytest.approx(212.3e6, rel=1e-3)
    # what a token keeps: 2 x (576 + 128) + 3 x 1,088 numbers, bf16
    assert family.cache_bytes_per_token(m) == 9344
    # the uncut model: 256 experts a sparse layer, the whole vocabulary
    whole = dict(PUBLISHED)
    assert family.total_params(whole) == pytest.approx(288e9, rel=0.03)
    assert family.model_config(whole).n_experts_held == 256


def test_decode_step_bytes_and_the_attentions_cost_at_the_cells_sizes():
    """64 full slots of 4,100 tokens: 1.95 GB of attention, dense, shared
    and head weights and routers, 6.04 GB of held experts of which a step
    touches 87%; the full layers read 2,048 rows a slot of 4,100 and score
    every index key, the sliding ones read 513."""
    m = cell_config()
    counters = {"occupancy_samples": [64] * 5,
                "live_kv_tokens_mean": 64 * 4100.0}
    share = 1.0 - (1.0 - 8 / 256) ** 64
    assert family.experts_touched_share(m, 64) == pytest.approx(share)
    attention = 2 * family.attention_params(m, False) \
        + 3 * family.attention_params(m, True)
    always = 2.0 * (attention + 3 * 5120 * 13824 + 4 * 3 * 5120 * 1536
                    + 5120 * 19008) + 4.0 * 4 * 5121 * 256
    experts = 2.0 * 4 * 32 * 3 * 5120 * 1536
    rows = 2.0 * (2 * (64 * 2048 * 576 + 64 * 4100 * 128)
                  + 3 * 64 * 513 * 1088)
    assert always == pytest.approx(1.951e9, rel=0.01)
    assert experts == pytest.approx(6.04e9, rel=0.001)
    assert family.attention_cache_bytes(m, counters) == pytest.approx(rows)
    assert family.decode_step_bytes(m, counters) == pytest.approx(
        always + experts * share + rows)
    cost = family.latent_attention_cost(m, counters)
    flops = (2 * (64 * 2048 * 128 * (512 + 64 + 512) * 2
                  + 64 * 128 * 512 * 256 * 2)
             + 3 * (64 * 513 * 64 * (1024 + 64 + 1024) * 2
                    + 64 * 64 * 1024 * 320 * 2)
             + 2 * 64 * 4100 * 64 * (128 * 2 + 3))
    nbytes = rows + 2.0 * (2 * 512 * 128 * 256 + 3 * 1024 * 64 * 320)
    assert cost["flops"] == pytest.approx(flops)
    assert cost["bytes"] == pytest.approx(nbytes)
    # a selected row: 241 operations a byte, the chip's ridge
    assert 128 * (512 + 64 + 512) * 2 / (576 * 2) == pytest.approx(241.8,
                                                                   abs=0.1)
    # contexts shorter than the window: every layer reads all of them
    short = dict(counters, live_kv_tokens_mean=64 * 300.0)
    assert family.attention_cache_bytes(m, short) == pytest.approx(
        64 * 300.0 * 9344)
    assert family.decode_step_bytes(m, {}) == pytest.approx(always)


# -- the new readers, on synthetic traces and spans --------------------------

DECODE = "jit_paged_decode_c16_w64(123)"
# a v5e trace's own names (my chip run, PR 40): the indexer's gather of a
# slot's index keys, its scores and their sort; the selected rows' gather,
# the absorbed scores and values; a sliding layer's; then what is neither
INDEX_OPS = (
    "%fusion.837 = bf16[4096,128,128]{2,1,0} fusion(bf16[2,2816,128,128] "
    "%fusion.836, s32[4096] %copy-done.7), kind=kCustom",
    "%fusion.839 = f32[64,8192]{1,0} fusion(bf16[64,8192,128] %bitcast.858, "
    "f32[64,64] %w, bf16[64,64,128] %q), kind=kOutput",
    "%sort.48 = (f32[64,8192]{1,0}, s32[64,8192]{1,0}) sort(f32[64,8192] "
    "%fusion.840, s32[64,8192] %iota), dimensions={1}, is_stable=true")
ATTENTION_OPS = (
    "%fusion.845 = bf16[131072,640]{1,0} fusion(bf16[2,2816,128,640] "
    "%fusion.835, s32[131072] %bitcast.939), kind=kCustom",
    "%fusion.850 = f32[64,128,2048]{2,1,0} fusion(bf16[64,2048,640] "
    "%bitcast.863, pred[64,2048] %copy-done.43, bf16[64,128,640] %q)",
    "%fusion.852 = bf16[64,128,512]{2,1,0} fusion(bf16[64,2048,640] "
    "%bitcast.863, f32[64,128,2048] %p, f32[64,128] %sum), kind=kOutput",
    "%fusion.903 = bf16[320,128,1152]{2,1,0} fusion(bf16[3,2816,128,1152] "
    "%fusion.901, s32[320] %reshape.1686), kind=kCustom",
    "%fusion.908 = f32[64,64,640]{2,1,0} fusion(bf16[64,640,1152] %rows, "
    "bf16[64,64,1152] %concatenate.444, pred[64,640] %mask), kind=kOutput",
    "%fusion.911 = bf16[64,8,8,128]{3,2,1,0} fusion(bf16[1024,64,320] "
    "%bitcast.945, bf16[64,64,1024] %fusion.910, f32[64,64] %copy.334)")
OTHER_OPS = (
    "%fusion.914 = f32[32,64,1536]{2,1,0} fusion(bf16[3,32,5120,1536] %w, "
    "s32[] %layer, bf16[64,5120] %fusion.913), kind=kOutput",
    "%fusion.867 = f32[64,19008]{1,0} fusion(bf16[5120,19008] %head, "
    "bf16[64,1,5120] %x, f32[5120] %norm, f32[64] %rms), kind=kOutput",
    "%fusion.830 = bf16[64,13824]{1,0} fusion(bf16[1,5120,13824] %w_up, "
    "bf16[64,13824] %fusion.829, bf16[64,5120] %h), kind=kOutput",
    "%fusion.821 = bf16[64,1,128,192]{2,0,3,1} fusion(bf16[128,192,1024] "
    "%wq_b, f32[64,1,1920] %fusion.806, f32[1024] %q_norm), kind=kOutput",
    "%fusion.912 = (f32[64], bf16[64,1,5120]) fusion(bf16[64,1,5120] %x, "
    "bf16[3,8192,5120] %wo, s32[] %layer), kind=kOutput",
    "%fusion.918 = bf16[64,1536]{1,0} fusion(bf16[64,5120] %fusion.913, "
    "bf16[3,5120,1536] %ws_gate, s32[] %layer), kind=kOutput")


def synthetic_trace(runs: int = 6) -> Trace:
    """``runs`` decode runs of 16 steps in 320 ms (20 ms a step), each
    step with 3 ms of the indexer's operations, 5 ms of the attention's
    own and 12 ms of everything else."""
    modules, ops = [], []
    for i in range(runs):
        t = 0.4 * i
        modules.append((DECODE, t, t + 0.320))
        for step in range(16):
            at = t + 0.020 * step
            for group, ms in ((INDEX_OPS, 3.0), (ATTENTION_OPS, 5.0),
                              (OTHER_OPS, 12.0)):
                for name in group:
                    span = ms * 1e-3 / len(group)
                    ops.append((name, at, at + span))
                    at += span
    return Trace([{"modules": modules, "ops": ops, "async_ops": []}], [],
                 extent_s=0.4 * runs)


def test_the_attentions_and_the_indexers_operations_are_told_by_their_shapes():
    ops = family.latent_attn_op(cell_config())
    assert all(ops["index"](n) and ops["attention"](n) for n in INDEX_OPS)
    assert all(ops["attention"](n) and not ops["index"](n)
               for n in ATTENTION_OPS)
    assert not any(ops["attention"](n) or ops["index"](n) for n in OTHER_OPS)
    is_expert_op = family.expert_ffn_op(cell_config())
    assert is_expert_op(OTHER_OPS[0]) and not any(
        is_expert_op(n) for n in INDEX_OPS + ATTENTION_OPS + OTHER_OPS[1:])


def test_the_trace_readers_on_a_synthetic_trace():
    m = cell_config()
    counters = {"occupancy_samples": [64] * 5,
                "live_kv_tokens_mean": 64 * 4100.0}
    run = type("Run", (), {"trace": synthetic_trace(), "config": m,
                           "counters": counters,
                           "device": {"kind": "TPU v5 lite"}})
    read = {n: harness.load_reader(n + SUFFIX) for n in OWN}
    assert inside.decode_program_step_ms(run.trace) == pytest.approx(20.0)
    assert read["latent_attn_share"](run) == pytest.approx(40.0)
    assert read["index_select_share"](run) == pytest.approx(37.5)
    # 8 ms a step against the cost's floor: 0.84 GB at 819 GB/s (the
    # operations, 0.12 TFLOP at 197 TFLOP/s, take less)
    cost = family.latent_attention_cost(m, counters)
    floor = max(cost["bytes"] / 819e9, cost["flops"] / 197e12)
    assert floor == pytest.approx(cost["bytes"] / 819e9)
    assert read["latent_attn_roofline"](run) == pytest.approx(
        100.0 * floor / 8e-3, rel=1e-6)
    assert 5.0 < read["latent_attn_roofline"](run) < 100.0
    # fewer runs than a median wants, no trace, a family with no such
    # layer (the parent's family files): nothing, and no error
    for trace, config in ((synthetic_trace(inside.MIN_SAMPLES - 1), m),
                          (None, m), (synthetic_trace(), {"family": "laguna"})):
        run.trace, run.config = trace, config
        assert [read[n](run) for n in OWN if n != "kv_selected_share"] == [
            None] * 3


def dispatch_span(i, **attrs):
    return {"name": "engine.dispatch_decode", "span_id": f"d{i}",
            "parent_id": "it", "duration": 0.001,
            "attrs": dict(live=64, slots=64, **attrs)}


def test_kv_selected_share_reads_the_dispatch_spans_own_counts(monkeypatch):
    run = type("Run", (), {"trace": None, "config": cell_config(),
                           "counters": {}})
    spans = [dispatch_span(i, kv_rows_full=64 * 4000 + 64 * i,
                           index_rows=64 * 4000 + 64 * i,
                           kv_rows_selected=64 * 2048) for i in range(6)]
    monkeypatch.setattr(program_spans, "engine_spans", lambda: spans)
    whole = sum(s["attrs"]["kv_rows_full"] for s in spans)
    assert harness.load_reader("kv_selected_share")(run) == \
        pytest.approx(100.0 * 6 * 64 * 2048 / whole)
    # the parent's spans count no selection; too few; none
    for other in ([dispatch_span(i, kv_rows_full=9) for i in range(6)],
                  spans[:inside.MIN_SAMPLES - 1], None):
        monkeypatch.setattr(program_spans, "engine_spans", lambda o=other: o)
        assert harness.load_reader("kv_selected_share")(run) is None


# -- the entries --------------------------------------------------------------

def test_the_cells_entries_keep_the_contract(bench):
    """Every clause of ``test_benchmark_json_keeps_the_contract`` for the
    entries of this cell: its configuration, the cell, and its metrics,
    each found by name with the cell under ``workloads``, wherever later
    PRs' entries stand."""
    cell = bench_pins.cell_entry(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "dots3-note-prev-ep8-d5", "note-backlog-transcript", 1)
    entry = bench_pins.config_entry(bench, cell["config"])
    config = cell_config()
    bench_pins.check_reduced(entry, config, PUBLISHED)
    assert sorted(entry["reduced"]) == sorted(REDUCED)
    assert config["name"] == entry["name"]
    assert set(bench_pins.reported(bench, CELL, "end_to_end")) == {
        "serve_tokens_per_s", "setup_s"}
    moved = bench_pins.entry(bench["end_to_end"], "serve_tokens_per_s")
    assert CELL in moved["workloads"] and moved["bound"] == 0.045
    mine = bench_pins.reports(bench, CELL, TWINS + tuple(OWN),
                              moves="serve_tokens_per_s")
    for stem, m in mine.items():
        if stem in OWN:
            assert (m["source"], m["better"], m["unit"], m["layer"]) == (
                *OWN[stem], "%", "kernels")
        else:                   # one entry, shared with the cell before
            assert "serve-code-gen" in m["workloads"]
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    assert (traffic["generator"], traffic["runner"]) == ("doc_backlog",
                                                         "serve_backlog")
    # every prompt in the 4,096 bucket, prompt plus answer within 5,120
    assert traffic["doc_tokens"]["min"] + traffic["question_tokens"]["min"] \
        > 2048 + 513
    assert traffic["doc_tokens"]["max"] + traffic["question_tokens"]["max"] \
        == 4096
    assert 4096 + traffic["answer_tokens"]["max"] == 5120 <= \
        config["system"]["max_len"]
    assert (traffic["askings"], traffic["docs_per_cycle"],
            traffic["wave_docs"], traffic["max_waiting"],
            traffic["ramp_s"]) == (4, 48, 2, 1, 45)


# -- the cell's runner, rehearsed at toy size --------------------------------

TOY_GEN = {
    "generator": "doc_backlog", "runner": "serve_backlog",
    "doc_tokens": {"dist": "uniform", "min": 64, "max": 112},
    "question_tokens": {"dist": "uniform", "min": 4, "max": 12},
    "answer_tokens": {"dist": "uniform", "min": 48, "max": 100},
    "askings": 4, "docs_per_cycle": 4, "wave_docs": 2, "max_waiting": 2,
    "ramp_s": 1, "trace_s": 4, "prefill_limits": bench_toy.LIMITS}
DRIVER = '''
import json, sys
sys.path.insert(0, ".")
from benchmark import harness
rc = harness.main(["--workload", sys.argv[1], "--seed", "4",
                   "--seconds", "5", "--trace", "1", "--rehearse"])
run = type("Run", (), {"trace": None, "counters": {},
                       "config": harness.load_cell(sys.argv[1])[2]})
values = {name: harness.load_reader(name)(run) for name in json.loads(
    sys.argv[2])}
print("inside " + json.dumps({"rc": rc, "values": values}))
'''
# the same family with the indexer dropped from the reference: what a
# comparison that could not see the selection would call correct
NO_INDEXER = '''
from benchmark.families.dots3_note import *  # noqa: F401,F403
from benchmark.families import dots3_note as _whole


def logits(config, params, tokens):
    return _whole.logits(config, params, tokens, indexer="none")
'''


def make_toy_note(tmp: str) -> str:
    """The toy copy with the CPU tests' toy dots3-note configuration (a
    selection of 32 keys and a window of 17 over pages of 16), a toy mix
    of ``note-backlog-transcript``'s shape (every context past both) and
    two cells of it, added as files and entries: one with the family as
    it is, one whose reference has no indexer."""
    import test_dots3_note as toy

    root = bench_toy.make_toy(tmp)
    with open(os.path.join(root, "benchmark", "families",
                           "dots3_note_no_indexer.py"), "w") as f:
        f.write(NO_INDEXER)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for tag, fam in (("", "dots3_note"), ("-blind", "dots3_note_no_indexer")):
        config = dict(
            toy.CONFIG, index_topk=32, sliding_window_size=17,
            name="toy-note-serve" + tag, family=fam,
            source="none: a toy for the CPU tests", reduced=[],
            torch_dtype="bfloat16", system={
                "max_batch": 4, "max_len": 256, "page_size": 16,
                "num_pages": 68, "kv_dtype": "bf16", "prefix_cache": True,
                "reference_check": {"prompt_tokens": 90, "shared_tokens": 64,
                                    "new_tokens": 6}})
        with open(os.path.join(root, "benchmark", "configs",
                               config["name"] + ".json"), "w") as f:
            json.dump(config, f)
        bench["configs"].append({
            "name": config["name"], "source": "none", "reduced": [],
            "why": "toy", "file": f"benchmark/configs/{config['name']}.json"})
        bench["workloads"].append({
            "name": "toy-note-gen" + tag, "config": config["name"],
            "traffic": "toy-note", "chips": 1, "why": "toy"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if CELL in m.get("workloads", ()):
                m["workloads"].append("toy-note-gen" + tag)
    with open(os.path.join(root, "benchmark", "traffic", "toy-note.json"),
              "w") as f:
        json.dump(TOY_GEN, f)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    return make_toy_note(str(tmp_path_factory.mktemp("toy_note")))


def rehearse(root: str, cell: str, names: list):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""),
               JAX_COMPILATION_CACHE_DIR=os.path.join(root, ".jax_cache"))
    r = subprocess.run(
        [sys.executable, "-c", DRIVER, cell, json.dumps(names)],
        cwd=root, env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    lines = r.stdout.strip().splitlines()
    return (json.loads(lines[-2].split(" ", 1)[1]),
            json.loads(lines[-1].split(" ", 1)[1]))


def test_toy_note_rehearses_the_cells_runner(toy_root):
    """The dots3-note stack through ``serve_backlog`` on the CPU, in bf16
    as the cell serves it: the float32 reference calls the engine's tokens
    correct (prompts past the selection and the window, prefix reuse over
    the latent pools, full slots), and the program's own counts reach the
    new reader and the twins. (Seed 4: a 64-wide stream in bf16 is a
    lottery the cell's 5,120-wide one is not; of seeds 3-6 three read a
    token gap under 0.01 and seed 3 read 0.26, CPU runs, PR 40.)"""
    names = ["kv_selected_share", "kv_window_read_share",
             "routed_here_share", "experts_touched_mean",
             "decode_active_share",
             "latent_attn_share", "index_select_share",
             "latent_attn_roofline", "expert_ffn_share"]
    rehearsal, got = rehearse(toy_root, "toy-note-gen", names)
    assert got["rc"] == 0
    assert rehearsal["correct"] is True and rehearsal["failed"] == 0
    assert rehearsal["attempted"] > 0
    # a rehearsal prints counters only
    assert set(rehearsal["metrics"]) == {"prefix_hit_share",
                                         "compiles_in_window"}
    assert rehearsal["metrics"]["compiles_in_window"]["value"] <= 1.0
    assert rehearsal["metrics"]["prefix_hit_share"]["value"] > 30.0
    values = got["values"]
    assert [values[n] for n in names[-4:]] == [None] * 4   # no device trace
    for name in names[:-4]:
        assert values[name] is not None, (name, values)
    # contexts of 70-220 tokens: 32 selected of them, a window of 17
    assert 15.0 < values["kv_selected_share"] < 50.0
    assert values["kv_window_read_share"] < 70.0
    assert values["experts_touched_mean"] <= 2.0


def test_a_reference_without_the_indexer_reads_not_correct(toy_root):
    """The same engine, the same tokens, a reference that attends over
    every key in its full layers: the comparison that decides ``correct``
    sees the selection."""
    rehearsal, got = rehearse(toy_root, "toy-note-gen-blind", [])
    assert got["rc"] == 0 and rehearsal["failed"] == 0
    assert rehearsal["correct"] is False
