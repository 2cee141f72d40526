"""The ``laguna`` model family of the benchmark (PR 33): its file passes the
family contract, the configuration is the published one but for its three
cuts (the published keys written HERE, not read from a catalog outside the
repo), its counts are pinned at the published widths, its three new
readers read synthetic traces and spans, each departure of its reference
alone makes the comparison that decides ``correct`` fail, and a toy
configuration of it rehearses ``serve-code-gen``'s runner on the CPU, in a
temporary copy to which the toy is added as new files and entries."""

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
from benchmark.families import laguna as family  # noqa: E402
from benchmark.trace import Trace  # noqa: E402

ROOT = bench_toy.REPO
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
# what every backlog cell reports, and what every cell of routed experts
CODE_TWINS = ("decode_program_step_ms", "decode_roofline",
              "prefill_program_share", "prefix_hit_share",
              "device_idle_share", "peak_hbm_gb", "engine_host_share",
              "decode_active_share", "decode_delivered_share",
              "decode_overrun_share", "prefill_fill_share",
              "expert_ffn_share", "experts_touched_mean",
              "expert_load_max_over_mean")
CODE_OWN = {"kv_window_read_share": ("program_span", "kernels"),
            "routed_here_share": ("program_span", "routed experts"),
            "paged_attn_roofline": ("device_trace", "kernels")}
PERIOD = ["full_attention"] + ["sliding_attention"] * 3
# https://huggingface.co/poolside/Laguna-S-2.1/blob/main/config.json, the
# keys that say something about the model's shape
PUBLISHED = {
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 3072,
    "intermediate_size": 12288, "num_hidden_layers": 48,
    "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 1048576, "attention_bias": False,
    "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 10,
    "moe_intermediate_size": 1024, "shared_expert_intermediate_size": 1024,
    "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [0],
    "tie_word_embeddings": False, "gating": "per-head",
    "sliding_window": 512,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
            "original_max_position_embeddings": 8192, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.4852030263919618,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    "layer_types": PERIOD * 12,
    "moe_apply_router_weight_on_input": False,
    "mlp_layer_types": ["dense"] + ["sparse"] * 47,
    "gating_types": ["per_head"] * 48, "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [48, 72, 72, 72] * 12,
    "moe_router_logit_softcapping": 0}
PER_LAYER_LISTS = ("layer_types", "mlp_layer_types", "gating_types",
                   "num_attention_heads_per_layer")


def cell_config() -> dict:
    with open(os.path.join(
            ROOT, "benchmark/configs/laguna-s-2.1-ep4-d5.json")) as f:
        return json.load(f)


# -- the family's file -------------------------------------------------------

def test_the_family_passes_the_api_check_and_keeps_off_the_program():
    assert systems.family({"family": "laguna"}) is family
    path = os.path.join(ROOT, "benchmark", "families", "laguna.py")
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


def test_the_configuration_is_the_published_one_but_for_its_three_cuts():
    import jax

    config = cell_config()
    assert config["source"] == ("https://huggingface.co/poolside/"
                                "Laguna-S-2.1/blob/main/config.json")
    differs = [k for k, v in PUBLISHED.items() if config.get(k) != v]
    assert sorted(differs) == sorted(
        ["num_hidden_layers", "num_experts", "vocab_size", *PER_LAYER_LISTS])
    # every key that differs is listed: the three cuts, and the four
    # per-layer lists that the depth cuts with it
    assert sorted(config["reduced"]) == sorted(
        ["num_hidden_layers", "num_experts", "vocab_size", *PER_LAYER_LISTS])
    assert {k: config["reduced_from"][k] for k in (
        "num_hidden_layers", "num_experts", "vocab_size")} == {
        "num_hidden_layers": 48, "num_experts": 256, "vocab_size": 100352}
    assert set(config["reduced_from"]) == set(config["reduced"])
    # the per-layer lists are the published ones' first five entries: the
    # leading dense full layer and one whole period
    for k in PER_LAYER_LISTS:
        assert config[k] == PUBLISHED[k][:5], k
    assert config["layer_types"][1:] == PERIOD[1:] + PERIOD[:1]
    assert config["expert_share"] == {"chips": 4, "index": 0,
                                      "num_experts_total": 256}
    # the floors: a whole period and four layers after the leading one, at
    # least 8 experts a layer, at least an eighth of the vocabulary
    assert config["num_experts"] == 64 and config["vocab_size"] * 4 == 100352
    assert {"gate", "scores", "qk_norm", "init", "expert_share"} <= set(
        config["assumed"])
    assert "v5e-32" in config["reduced_why"] and "64" in config["deployment"]
    # the program's weights are the family's count, leaf for leaf
    cfg = family.model_config(config)
    assert (cfg.n_experts, cfg.n_experts_held, cfg.first_expert) == (256, 64, 0)
    assert (cfg.n_heads, cfg.n_heads_sliding, cfg.window) == (48, 72, 512)
    shapes = jax.eval_shape(lambda k: family.init_params(cfg, k),
                            jax.random.key(0))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == \
        family.total_params(config) == 3_002_016_768
    assert shapes["blocks"]["layers1-3"]["router"].shape == (3, 3072, 256)
    assert shapes["blocks"]["layers1-3"]["wi_gate"].shape == (
        3, 64, 3072, 1024)
    system = config["system"]
    assert system["max_batch"] == 64 and system["max_len"] == 4096
    assert system["num_pages"] >= 1984 and system["page_size"] == 128
    check = system["reference_check"]
    assert check["shared_tokens"] > 512 and check["shared_tokens"] % 128 == 0
    assert check["prompt_tokens"] - check["shared_tokens"] < 512


def test_counts_at_the_published_widths():
    m = cell_config()
    assert family.expert_params(m) == 3 * 3072 * 1024           # 9.44M
    assert family.attention_params(m, 48) == 3072 * (8192 + 48) + 6144 * 3072
    assert family.attention_params(m, 72) == 3072 * (11264 + 72) + 9216 * 3072
    assert family.attention_layer_counts(m) == (2, 3)
    assert family.kv_bytes_per_token_layer(m) == 4096
    assert family.router_params(m) == 3072 * 256
    # the uncut model: 256 experts a sparse layer, the whole vocabulary
    whole = dict(PUBLISHED)
    assert family.total_params(whole) == pytest.approx(117.6e9, rel=0.01)
    assert family.model_config(whole).n_experts_held == 256


def test_decode_step_bytes_at_the_cells_sizes():
    """64 full slots of 2,500 tokens: 1.17 GB of attention, dense, shared
    and head weights and routers, 4.83 GB of held experts of which a step
    touches 92%, 1.31 GB of keys and values in the two full layers and
    0.40 GB in the three sliding ones (64 x 512 tokens each)."""
    m = cell_config()
    counters = {"occupancy_samples": [64] * 5,
                "live_kv_tokens_mean": 64 * 2500.0}
    share = 1.0 - (1.0 - 10 / 256) ** 64
    assert family.experts_touched_share(m, 64) == pytest.approx(share)
    assert share == pytest.approx(0.9219, abs=1e-4)
    attention = 2 * family.attention_params(m, 48) + 3 * family.attention_params(m, 72)
    always = 2.0 * (attention + 3 * 3072 * 12288 + 4 * 3 * 3072 * 1024
                    + 3072 * 25088) + 4.0 * 4 * 3072 * 256
    experts = 2.0 * 4 * 64 * 3 * 3072 * 1024
    kv = 4096 * (2 * 64 * 2500.0 + 3 * 64 * 512)
    assert always == pytest.approx(1.024e9, rel=0.01)
    assert experts == pytest.approx(4.832e9, rel=0.001)
    assert kv == pytest.approx(1.713e9, rel=0.001)
    assert family.attention_kv_bytes(m, counters) == pytest.approx(kv)
    assert family.decode_step_bytes(m, counters) == pytest.approx(
        always + experts * share + kv)
    # contexts shorter than the window: every layer reads all of them
    short = dict(counters, live_kv_tokens_mean=64 * 300.0)
    assert family.attention_kv_bytes(m, short) == pytest.approx(
        4096 * 5 * 64 * 300.0)
    assert family.decode_step_bytes(m, {}) == pytest.approx(always)


# -- the new readers, on synthetic traces and spans --------------------------

DECODE = "jit_paged_decode_c16_w32(123)"
KERNEL_OP = ('%paged_decode_attn.{} = bf16[64,72,128]{{2,1,0}} custom-call('
             's32[1] %l), custom_call_target="tpu_custom_call"')
EXPERT_OPS = (
    "%fusion.248 = f32[64,64,1024]{2,1,0} fusion(bf16[3,64,3072,1024] %w, "
    "s32[] %layer, bf16[64,3072] %h), kind=kOutput",
    "%fusion.2 = f32[64,3072]{1,0} fusion(bf16[64,64,1024] %h, "
    "bf16[1,64,1024,3072] %w), kind=kOutput",
    "%fusion.3 = f32[64,256]{1,0} fusion(f32[64,3072] %x, "
    "f32[3,3072,256] %router), kind=kOutput")
OTHER_OPS = (
    "%fusion.7 = f32[64,25088]{1,0} fusion(bf16[64,3072] %x, "
    "bf16[3072,25088] %head), kind=kOutput",
    "%fusion.9 = bf16[64,1024]{1,0} fusion(bf16[64,3072] %h, "
    "bf16[3,3072,1024] %ws_gate), kind=kOutput",
    "%fusion.11 = bf16[64,1,11264]{2,1,0} fusion(bf16[64,3072] %h, "
    "bf16[3,3072,11264] %wqkv), kind=kOutput")


def synthetic_trace(runs: int = 6) -> Trace:
    """``runs`` decode runs of 16 steps in 160 ms, each with 80 calls of
    the kernel (5 layers x 16 steps) of 0.4 ms."""
    modules, ops = [], []
    for i in range(runs):
        t = 0.2 * i
        modules.append((DECODE, t, t + 0.160))
        for j in range(80):
            ops.append((KERNEL_OP.format(j), t + 0.002 * j,
                        t + 0.002 * j + 0.0004))
    return Trace([{"modules": modules, "ops": ops, "async_ops": []}], [],
                 extent_s=0.2 * runs)


def test_the_routed_experts_operations_are_told_by_their_shapes():
    is_expert_op = family.expert_ffn_op(cell_config())
    assert all(is_expert_op(n) for n in EXPERT_OPS)
    assert not any(is_expert_op(n) for n in OTHER_OPS)
    assert is_expert_op("%ragged-dot = f32[40960,1024] custom-call()")


def test_paged_attn_roofline_is_the_familys_bytes_over_the_kernels_time():
    m = cell_config()
    counters = {"occupancy_samples": [64] * 5,
                "live_kv_tokens_mean": 64 * 2500.0}
    run = type("Run", (), {"trace": synthetic_trace(), "config": m,
                           "counters": counters,
                           "device": {"kind": "TPU v5 lite"}})
    # a step's five calls take 2.0 ms; 1.713 GB at 819 GB/s take 2.09 ms
    got = harness.load_reader("paged_attn_roofline")(run)
    assert inside.decode_program_step_ms(run.trace) == pytest.approx(10.0)
    assert got == pytest.approx(100.0 * 1.713e9 / 819e9 / 2.0e-3, rel=0.01)
    # fewer runs than a median wants, no trace, a family that counts no
    # such bytes, the parent's family file: nothing, and no error
    run.trace = synthetic_trace(inside.MIN_SAMPLES - 1)
    assert harness.load_reader("paged_attn_roofline")(run) is None
    run.trace = None
    assert harness.load_reader("paged_attn_roofline")(run) is None
    run.trace, run.config = synthetic_trace(), {"family": "dots3_note"}
    assert harness.load_reader("paged_attn_roofline")(run) is None


def dispatch_span(i, **attrs):
    return {"name": "engine.dispatch_decode", "span_id": f"d{i}",
            "parent_id": "it", "duration": 0.001,
            "attrs": dict(live=64, slots=64, **attrs)}


def emit_span(i, **attrs):
    return {"name": "engine.emit", "span_id": f"e{i}", "parent_id": "it",
            "duration": 0.001, "attrs": attrs}


def test_the_span_readers_read_the_programs_own_counts(monkeypatch):
    from benchmark import program_spans

    spans = [dispatch_span(i, kv_rows_full=64 * 2000 + 1000 * i,
                           kv_rows_window=64 * 512) for i in range(6)]
    spans += [emit_span(i, what="chunk", tokens=1024,
                        routed_here_share=0.24 + 0.004 * i,
                        experts_touched=58.0) for i in range(6)]
    spans.append(dispatch_span(9))               # a program with no window
    spans.append(emit_span(9, what="firsts", tokens=2))
    monkeypatch.setattr(program_spans, "engine_spans", lambda: spans)
    run = type("Run", (), {"trace": None, "config": cell_config()})
    full = sum(64 * 2000 + 1000 * i for i in range(6))
    want = 100.0 * (2 * full + 3 * 6 * 64 * 512) / (5 * full)
    assert harness.load_reader("kv_window_read_share")(run) == \
        pytest.approx(want)
    assert 54.0 < want < 56.0
    assert harness.load_reader("routed_here_share")(run) == \
        pytest.approx(25.0)
    # too few chunks, or a program that counts neither (the parent's)
    monkeypatch.setattr(program_spans, "engine_spans", lambda: spans[:3])
    assert harness.load_reader("kv_window_read_share")(run) is None
    monkeypatch.setattr(program_spans, "engine_spans", lambda: spans[-2:])
    assert harness.load_reader("routed_here_share")(run) is None
    monkeypatch.setattr(program_spans, "engine_spans", lambda: None)
    assert harness.load_reader("kv_window_read_share")(run) is None
    assert harness.load_reader("routed_here_share")(run) is None
    run.config = {"family": "llama"}
    monkeypatch.setattr(program_spans, "engine_spans", lambda: spans)
    assert harness.load_reader("kv_window_read_share")(run) is None


# -- the entries --------------------------------------------------------------

def test_the_cells_entries_keep_the_contract(bench):
    """Every clause of ``test_benchmark_json_keeps_the_contract`` for the
    entries of this cell, each found by its NAME with the cell under its
    ``workloads``: what a later PR appends behind them, or adds to the
    cell, turns nothing here."""
    cell = bench_pins.cell_entry(bench, "serve-code-gen")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "laguna-s-2.1-ep4-d5", "code-backlog-longgen", 1)
    entry = bench_pins.config_entry(bench, cell["config"])
    config = cell_config()
    bench_pins.check_reduced(entry, config, PUBLISHED)
    assert set(bench_pins.reported(bench, "serve-code-gen",
                                   "end_to_end")) == {
        "serve_tokens_per_s", "setup_s"}
    moved = bench_pins.entry(bench["end_to_end"], "serve_tokens_per_s")
    assert "serve-code-gen" in moved["workloads"] and moved["bound"] == 0.045
    mine = bench_pins.reports(bench, "serve-code-gen",
                              CODE_TWINS + tuple(CODE_OWN),
                              moves="serve_tokens_per_s")
    for stem, m in mine.items():
        if stem in CODE_OWN:
            assert (m["source"], m["layer"]) == CODE_OWN[stem]
            assert m["unit"] == "%"
        if stem in CODE_TWINS:      # one entry, shared with the cell before
            assert "serve-moe-gen" in m["workloads"]
    assert "compiles_in_window" in bench_pins.reported(bench,
                                                       "serve-code-gen")
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "code-backlog-longgen.json")) as f:
        traffic = json.load(f)
    lengths = {k: (traffic[k]["min"], traffic[k]["max"])
               for k in ("doc_tokens", "question_tokens", "answer_tokens")}
    assert lengths == {"doc_tokens": (1024, 1792),
                       "question_tokens": (32, 128),
                       "answer_tokens": (1024, 1920)}
    assert sum(hi for _, hi in lengths.values()) == 3840        # 30 pages
    assert (traffic["generator"], traffic["runner"]) == (
        "doc_backlog", "serve_backlog")
    assert (traffic["askings"], traffic["docs_per_cycle"],
            traffic["wave_docs"], traffic["max_waiting"]) == (4, 48, 16, 2)
    # every slot's largest reservation fits the pool at once
    system = config["system"]
    assert system["max_batch"] * (3840 // 128 + 1) <= system["num_pages"]
    # and the warm-up's grid holds the two-prompt cold prefill the
    # hand-over of two requests can bring
    prefill, decode = serving.warm_cells(
        [(np.ones(1920, np.int32), 1920)], system, traffic["prefill_limits"])
    assert (2, 2048, 16) in prefill and decode == {32}


# -- each departure alone fails the comparison that decides ``correct`` ------

sys.path.insert(0, os.path.join(ROOT, "tests"))


@pytest.fixture(scope="module")
def served():
    """Two prompts through the toy engine, as ``serving.prepare_engine``
    serves its reference check: the second reuses the first's pages."""
    import test_laguna as toy

    from ray_tpu.serve.paged_llm import PagedLLMEngine

    cfg = family.model_config(toy.CONFIG)
    params = toy.make_params(cfg)
    rng = np.random.default_rng(2)
    first = rng.integers(1, 128, 50, dtype=np.int32)
    second = np.concatenate([first[:32],
                             rng.integers(1, 128, 19, dtype=np.int32)])
    eng = PagedLLMEngine(cfg=cfg, params=params, max_batch=2, max_len=128,
                         page_size=8, num_pages=40)
    eng.start()
    out = [(p, serving.collect(eng, eng.submit(p, max_new_tokens=12)))
           for p in (first, second)]
    eng.stop()
    return toy.CONFIG, params, out


@pytest.mark.parametrize("departure", [
    None, {"window": None}, {"gate": "none"}, {"routing_scale": 1.0},
    {"scores": "sigmoid"}, {"yarn": False}],
    ids=["published", "window", "gate", "routing_scale", "scores", "yarn"])
def test_each_departure_alone_reads_not_correct(served, departure):
    config, params, out = served

    def logits(*args):
        return family.logits(*args, **(departure or {}))

    gap = max(reference.token_gap(logits, config, params, prompt, tokens)[0]
              for prompt, tokens in out)
    if departure is None:
        assert gap <= serving.TOKEN_GAP_TOL
    else:
        assert gap > 3 * serving.TOKEN_GAP_TOL


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
rc = harness.main(["--workload", "toy-code-gen", "--seed", sys.argv[1],
                   "--seconds", "5", "--trace", "1", "--rehearse"])
run = type("Run", (), {"trace": None, "counters": {},
                       "config": harness.load_cell("toy-code-gen")[2]})
values = {name: harness.load_reader(name)(run) for name in json.loads(
    sys.argv[2])}
print("inside " + json.dumps({"rc": rc, "values": values}))
'''


def make_toy_laguna(tmp: str) -> str:
    """The toy copy with the CPU tests' toy Laguna configuration (window
    16 over pages of 16), a toy mix of ``code-backlog-longgen``'s shape
    (contexts grow to several windows; one decode table serves the run)
    and their cell, added as files and entries; the cell reports what
    ``serve-code-gen`` reports."""
    import test_laguna as toy

    root = bench_toy.make_toy(tmp)
    config = dict(toy.CONFIG, name="toy-laguna-serve", family="laguna",
                  source="none: a toy for the CPU tests", reduced=[],
                  torch_dtype="bfloat16", system={
                      "max_batch": 4, "max_len": 256, "page_size": 16,
                      "num_pages": 68, "kv_dtype": "bf16",
                      "prefix_cache": True,
                      "reference_check": {"prompt_tokens": 90,
                                          "shared_tokens": 64,
                                          "new_tokens": 6}})
    for name, data in (("configs/toy-laguna-serve", config),
                       ("traffic/toy-code", TOY_GEN)):
        with open(os.path.join(root, "benchmark", name + ".json"), "w") as f:
            json.dump(data, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "toy-laguna-serve", "source": "none", "reduced": [],
        "why": "toy", "file": "benchmark/configs/toy-laguna-serve.json"})
    bench["workloads"].append({
        "name": "toy-code-gen", "config": "toy-laguna-serve",
        "traffic": "toy-code", "chips": 1, "why": "toy"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "serve-code-gen" in m.get("workloads", ()):
            m["workloads"].append("toy-code-gen")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def test_toy_laguna_rehearses_the_cells_runner(tmp_path):
    """The Laguna stack through ``serve_backlog`` on the CPU, in bf16 as
    the cell serves it: the float32 reference calls the engine's tokens
    correct (prompts past the window, prefix reuse, full slots), and the
    program's own counts reach the new readers: the sliding layers read
    their window of contexts several windows long, and about a quarter of
    the tokens' choices fall on the two experts of eight held here."""
    root = make_toy_laguna(str(tmp_path))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""),
               JAX_COMPILATION_CACHE_DIR=os.path.join(root, ".jax_cache"))
    names = ["kv_window_read_share", "routed_here_share",
             "experts_touched_mean", "expert_load_max_over_mean",
             "decode_active_share",
             "paged_attn_roofline", "expert_ffn_share"]
    r = subprocess.run(
        [sys.executable, "-c", DRIVER, "3", json.dumps(names)],
        cwd=root, env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    lines = r.stdout.strip().splitlines()
    rehearsal = json.loads(lines[-2].split(" ", 1)[1])
    got = json.loads(lines[-1].split(" ", 1)[1])
    assert got["rc"] == 0
    assert rehearsal["correct"] is True and rehearsal["failed"] == 0
    assert rehearsal["attempted"] > 0
    # a rehearsal prints counters only
    assert set(rehearsal["metrics"]) == {"prefix_hit_share",
                                         "compiles_in_window"}
    assert rehearsal["metrics"]["compiles_in_window"]["value"] <= 1.0
    assert rehearsal["metrics"]["prefix_hit_share"]["value"] > 30.0
    values = got["values"]
    assert values["paged_attn_roofline"] is None    # no device trace
    assert values["expert_ffn_share"] is None
    for name in names[:-2]:
        assert values[name] is not None, (name, values)
    # contexts of 70-220 tokens against a window of 16 in 3 of 5 layers
    assert 40.0 < values["kv_window_read_share"] < 60.0
    assert 10.0 < values["routed_here_share"] < 45.0
    assert values["experts_touched_mean"] <= 2.0
