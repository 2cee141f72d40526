"""The ``olmoe`` model family of the benchmark (PR 28): its file passes the
family contract, its counts are pinned at the published widths, its readers
read synthetic traces and spans, and a toy configuration of it rehearses
``serve-moe-gen``'s runner on the CPU, in a temporary copy to which the toy
is added as new files and entries."""

import ast
import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_pins  # noqa: E402
import bench_toy  # noqa: E402

from benchmark import experts, harness, inside, systems  # noqa: E402
from benchmark.families import olmoe as family  # noqa: E402
from benchmark.trace import Trace  # noqa: E402

ROOT = bench_toy.REPO
MOE_TWINS = ("decode_program_step_ms", "decode_roofline",
             "prefill_program_share", "prefix_hit_share",
             "device_idle_share", "peak_hbm_gb", "engine_host_share",
             "decode_active_share", "decode_delivered_share",
             "decode_overrun_share", "prefill_fill_share",
             "paged_attn_roofline")
MOE_OWN = ("expert_ffn_share", "experts_touched_mean",
           "expert_load_max_over_mean")
# https://huggingface.co/allenai/OLMoE-1B-7B-0125-Instruct/blob/main/
# config.json, the keys that say something about the model's shape: what
# the catalog's row held while it had one (the catalog beside the
# ``model-configs`` guide lists other architectures now)
PUBLISHED = {
    "attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 1024,
    "max_position_embeddings": 4096, "norm_topk_prob": False,
    "num_attention_heads": 16, "num_experts": 64, "num_experts_per_tok": 8,
    "num_hidden_layers": 16, "num_key_value_heads": 16,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000.0,
    "tie_word_embeddings": False, "vocab_size": 50304}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def published(layers: int = 16) -> dict:
    with open(os.path.join(
            ROOT, "benchmark/configs/olmoe-1b-7b-0125-d10.json")) as f:
        return dict(json.load(f), num_hidden_layers=layers)


# -- the family's file -------------------------------------------------------

def test_the_family_passes_the_api_check_and_keeps_off_the_program():
    assert systems.family({"family": "olmoe"}) is family
    path = os.path.join(ROOT, "benchmark", "families", "olmoe.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    top = [n for n in tree.body if isinstance(n, (ast.Import, ast.ImportFrom))]
    names = [a.name for n in top if isinstance(n, ast.Import)
             for a in n.names] + [n.module for n in top
                                  if isinstance(n, ast.ImportFrom)]
    assert names and not any(n.split(".")[0] in ("ray_tpu", "benchmark")
                             for n in names)
    # the program is imported inside the two adapter functions only
    inner = {f.name for f in tree.body if isinstance(f, ast.FunctionDef)
             for n in ast.walk(f) if isinstance(n, ast.ImportFrom)
             and n.module.startswith("ray_tpu")}
    assert inner == {"model_config", "init_params"}


def test_the_configuration_is_the_published_one_but_for_depth():
    import jax

    config = published(10)
    # against the catalog's row where the catalog is readable and has
    # one; it is a list of architectures the driver draws from, and a
    # model the benchmark already has may have left it (it has: no row of
    # this name since before PR 28's seed), so the published keys above
    # are what the file is held to either way
    want, source = PUBLISHED, ("https://huggingface.co/allenai/"
                               "OLMoE-1B-7B-0125-Instruct/blob/main/"
                               "config.json")
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            rows = [json.loads(line) for line in f]
        for row in rows:
            if row["name"] == "OLMoE-1B-7B-0125-Instruct":
                want, source = dict(PUBLISHED, **row["config"]), \
                    row["source_url"]
    assert config["source"] == source
    differs = [k for k, v in want.items() if config.get(k) != v]
    assert differs == config["reduced"] == ["num_hidden_layers"]
    bench_pins.check_reduced(bench_pins.config_entry(
        bench_pins.committed(), "olmoe-1b-7b-0125-d10"), config, want)
    assert config["reduced_from"] == {"num_hidden_layers": 16}
    assert {"qk_norm", "head_dim", "init"} <= set(config["assumed"])
    # the program's weights are the family's count, leaf for leaf
    cfg = family.model_config(config)
    shapes = jax.eval_shape(lambda k: family.init_params(cfg, k),
                            jax.random.key(0))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == \
        family.total_params(config) == 4_401_743_872
    system = config["system"]
    assert system["max_batch"] == 32 and system["max_len"] == 1024
    assert system["num_pages"] >= 288 and system["page_size"] == 128


def test_counts_at_the_published_widths():
    m = published(16)
    assert family.layer_params(m) == 419_569_664           # 419.6M a layer
    assert family.total_params(m) == 6_919_161_856         # 6.92B
    assert family.active_params(m) == 1_282_017_280        # 1.3B a token
    assert family.kv_bytes_per_token_layer(m) == 8192
    assert family.expert_params(m) == 3 * 2048 * 1024
    # a train step counts 8 experts a token, not 64
    active = family.matmul_params_active(m)
    assert active == 16 * (4 * 2048 * 2048 + 2048 * 64
                           + 8 * 3 * 2048 * 1024) + 2048 * 50304
    assert family.train_flops_per_token(m, 2048) == pytest.approx(
        6.0 * active + 3.0 * 16 * 0.5 * 4.0 * 2048 * 2048)
    cost = family.flash_train_cost(m, 2, 2048)
    assert cost["flops"] == pytest.approx(3.5 * 16 * 2 * 0.5 * 4.0
                                          * 2048 * 2048 * 2048)
    assert cost["bytes"] == 2 * 2048 * 16 * 2 * 12 * 2048


def test_decode_step_bytes_at_the_cells_depth():
    """d10 at 32 full slots of 700 tokens: 0.54 GB of attention, head and
    router weights, 8.05 GB of experts of which a step touches 98.6%, and
    1.8 GB of live keys and values."""
    m = published(10)
    counters = {"occupancy_samples": [32] * 5,
                "live_kv_tokens_mean": 32 * 700.0}
    share = 1.0 - (1.0 - 8 / 64) ** 32
    assert family.experts_touched_share(m, 32) == pytest.approx(share)
    assert share == pytest.approx(0.98606, abs=1e-5)
    always = 2.0 * (10 * 4 * 2048 * 2048 + 2048 * 50304) + 4.0 * 10 * 2048 * 64
    all_experts = 2.0 * 10 * 64 * 3 * 2048 * 1024
    cache = 10 * 8192 * 32 * 700.0
    assert always == pytest.approx(0.547e9, rel=0.01)
    assert all_experts == pytest.approx(8.053e9, rel=0.001)
    assert family.decode_step_bytes(m, counters) == pytest.approx(
        always + all_experts * share + cache)
    # fewer live slots reach fewer experts: 8 slots, 65.6%
    few = dict(counters, occupancy_samples=[8] * 5)
    assert family.decode_step_bytes(m, few) == pytest.approx(
        always + all_experts * (1.0 - 0.875 ** 8) + cache)
    assert family.decode_step_bytes(m, {}) == pytest.approx(always)


# -- the new readers, on synthetic traces and spans --------------------------

DECODE = "jit_paged_decode_c8_w8(123)"
PREFILL = "jit_paged_prefill_w4(456)"
EXPERT_OPS = (
    "%fusion.248 = f32[64,32,1024]{2,1,0} fusion(bf16[10,64,2048,1024] %w, "
    "s32[] %layer, bf16[32,2048] %h), kind=kOutput",
    "%fusion.9 = bf16[32,64,1024]{2,0,1} fusion(f32[64,32,1024] %g, "
    "f32[32,64] %weights), kind=kLoop",
    "%fusion.2 = f32[32,2048]{1,0} fusion(bf16[32,64,1024] %h, "
    "bf16[10,64,1024,2048] %w), kind=kOutput",
    "%fusion.3 = f32[32,64]{1,0} fusion(f32[32,2048] %x, "
    "f32[10,2048,64] %router), kind=kOutput")
RAGGED = ("%ragged-dot-none = f32[16384,1024]{1,0} custom-call(s32[1] %m, "
          "bf16[16384,2048] %rows, bf16[64,2048,1024] %w), "
          'custom_call_target="tpu_custom_call"')
OTHER_OPS = (
    "%fusion.237 = bf16[256,128,16,128]{3,2,1,0} fusion(bf16[10,352,128,16,128]"
    " %pool, s32[32,8] %table), kind=kLoop",
    "%fusion.8 = f32[32,1,64]{2,1,0} fusion(s32[32] %pos), kind=kLoop",
    "%fusion.7 = f32[32,50304]{1,0} fusion(bf16[32,2048] %x, "
    "bf16[2048,50304] %head), kind=kOutput")
WHILE = ("%while.1 = (bf16[10,64,2048,1024], s32[]) while((bf16[10,64,2048,"
         "1024], s32[]) %t), condition=%c, body=%b")


def synthetic_trace(runs: int = 6) -> Trace:
    """``runs`` decode runs of 10 ms, each with a loop over it, 4 ms of
    the experts' operations and 3 ms of others, and one prefill run that
    holds an expert operation of its own."""
    modules, ops = [], []
    for i in range(runs):
        t = 0.02 * i
        modules.append((DECODE, t, t + 0.010))
        ops.append((WHILE, t, t + 0.010))
        for j, name in enumerate(EXPERT_OPS):
            ops.append((name, t + 0.001 * j, t + 0.001 * (j + 1)))
        for j, name in enumerate(OTHER_OPS):
            ops.append((name, t + 0.005 + 0.001 * j, t + 0.006 + 0.001 * j))
    t = 0.02 * runs
    modules.append((PREFILL, t, t + 0.010))
    ops.append((EXPERT_OPS[0], t, t + 0.008))
    return Trace([{"modules": modules, "ops": ops, "async_ops": []}], [],
                 extent_s=t + 0.010)


def test_expert_ffn_share_reads_the_decode_runs_alone():
    is_expert_op = family.expert_ffn_op(published(10))
    assert all(is_expert_op(n) for n in EXPERT_OPS + (RAGGED,))
    assert not any(is_expert_op(n) for n in OTHER_OPS)
    assert is_expert_op(WHILE)         # by its shapes: the reader's to skip
    trace = synthetic_trace()
    assert experts.expert_ffn_share(trace, is_expert_op) == \
        pytest.approx(40.0)
    # fewer decode runs than a median wants, no trace, no device: nothing
    assert experts.expert_ffn_share(synthetic_trace(inside.MIN_SAMPLES - 1),
                                    is_expert_op) is None
    assert experts.expert_ffn_share(None, is_expert_op) is None
    assert experts.expert_ffn_share(Trace([], [], 1.0), is_expert_op) is None
    run = type("Run", (), {"trace": trace, "config": published(10)})
    assert harness.load_reader("expert_ffn_share")(run) == \
        pytest.approx(40.0)
    # a family without routed experts has no such layer
    run.config = {"family": "llama"}
    assert harness.load_reader("expert_ffn_share")(run) is None


def emit_span(i, **attrs):
    return {"name": "engine.emit", "span_id": f"e{i}", "parent_id": "it",
            "duration": 0.001, "attrs": attrs}


def test_routing_counts_are_the_chunks_means(monkeypatch):
    spans = [emit_span(i, what="chunk", tokens=256, experts_touched=60.0 + i,
                       expert_load_max_over_mean=2.0 + 0.1 * i)
             for i in range(6)]
    spans += [emit_span(9, what="firsts", tokens=2),
              emit_span(10, what="chunk", tokens=8),      # a dense block's
              {"name": "engine.dispatch_decode", "span_id": "d",
               "duration": 0.001, "attrs": {"live": 32, "slots": 32}}]
    assert experts.chunk_stat_mean(spans, "experts_touched") == \
        pytest.approx(62.5)
    assert experts.chunk_stat_mean(spans, "expert_load_max_over_mean") == \
        pytest.approx(2.25)
    assert experts.chunk_stat_mean(spans[:3], "experts_touched") is None
    assert experts.chunk_stat_mean(None, "experts_touched") is None
    from benchmark import program_spans

    monkeypatch.setattr(program_spans, "engine_spans", lambda: spans)
    run = type("Run", (), {"trace": None})
    assert harness.load_reader("experts_touched_mean")(run) == \
        pytest.approx(62.5)
    assert harness.load_reader("expert_load_max_over_mean")(run) == \
        pytest.approx(2.25)
    # a program that records no such count (the parent's): nothing, quietly
    monkeypatch.setattr(program_spans, "engine_spans", lambda: None)
    assert harness.load_reader("experts_touched_mean")(run) is None
    assert harness.load_reader("expert_load_max_over_mean")(run) is None


def test_the_cells_entries(bench):
    """Each found by its name with the cell under ``workloads``: what a
    later PR adds to the cell or appends behind turns nothing here."""
    cell = bench_pins.cell_entry(bench, "serve-moe-gen")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "olmoe-1b-7b-0125-d10", "gen-backlog-fewshot", 1)
    assert set(bench_pins.reported(bench, "serve-moe-gen",
                                   "end_to_end")) == {
        "serve_tokens_per_s", "setup_s"}
    mine = bench_pins.reports(bench, "serve-moe-gen", MOE_TWINS + MOE_OWN,
                              moves="serve_tokens_per_s")
    for stem in MOE_TWINS:          # one entry, shared with the cell before
        assert "serve-doc" in mine[stem]["workloads"]
    for stem in MOE_OWN:
        assert mine[stem]["layer"] == "routed experts"
    assert "compiles_in_window" in bench_pins.reported(bench,
                                                       "serve-moe-gen")
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "gen-backlog-fewshot.json")) as f:
        traffic = json.load(f)
    lengths = {k: (traffic[k]["min"], traffic[k]["max"])
               for k in ("doc_tokens", "question_tokens", "answer_tokens")}
    assert lengths == {"doc_tokens": (128, 384), "question_tokens": (32, 64),
                       "answer_tokens": (320, 560)}
    assert sum(hi for _, hi in lengths.values()) == 1008     # 8 pages
    assert (traffic["askings"], traffic["docs_per_cycle"],
            traffic["wave_docs"], traffic["max_waiting"], traffic["ramp_s"],
            traffic["trace_s"]) == (4, 48, 16, 3, 15, 6)
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "doc-backlog.json")) as f:
        assert traffic["prefill_limits"] == json.load(f)["prefill_limits"]


# -- the cell's runner, rehearsed at toy size --------------------------------

TOY_OLMOE = {
    "name": "toy-olmoe-serve", "family": "olmoe",
    "source": "none: a toy for the CPU tests", "reduced": [],
    "hidden_size": 64, "intermediate_size": 32, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
    "num_experts": 8, "num_experts_per_tok": 3, "norm_topk_prob": False,
    "clip_qkv": None, "vocab_size": 128, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
    "torch_dtype": "bfloat16",
    "system": {"max_batch": 4, "max_len": 256, "page_size": 16,
               "num_pages": 68, "kv_dtype": "bf16", "prefix_cache": True,
               "reference_check": {"prompt_tokens": 90, "shared_tokens": 64,
                                   "new_tokens": 6}}}
TOY_GEN = {
    "generator": "doc_backlog", "runner": "serve_backlog",
    "doc_tokens": {"dist": "uniform", "min": 100, "max": 160},
    "question_tokens": {"dist": "uniform", "min": 4, "max": 12},
    "answer_tokens": {"dist": "uniform", "min": 24, "max": 48},
    "askings": 4, "docs_per_cycle": 4, "wave_docs": 2, "max_waiting": 2,
    "ramp_s": 1, "trace_s": 4, "prefill_limits": bench_toy.LIMITS}
DRIVER = '''
import json, sys
sys.path.insert(0, ".")
from benchmark import harness
rc = harness.main(["--workload", "toy-moe-gen", "--seed", sys.argv[1],
                   "--seconds", "5", "--trace", "1", "--rehearse"])
run = type("Run", (), {"trace": None,
                       "config": harness.load_cell("toy-moe-gen")[2]})
values = {name: harness.load_reader(name)(run) for name in json.loads(
    sys.argv[2])}
print("inside " + json.dumps({"rc": rc, "values": values}))
'''


def make_toy_moe(tmp: str) -> str:
    """The toy copy with a toy OLMoE configuration, a toy mix of
    ``gen-backlog-fewshot``'s shape (every request reserves 9 to 15 pages,
    so one decode window serves the whole run, as 8 pages do in the cell)
    and their cell, added as files and entries; the cell reports what
    ``serve-moe-gen`` reports."""
    root = bench_toy.make_toy(tmp)
    for name, data in (("configs/toy-olmoe-serve", TOY_OLMOE),
                       ("traffic/toy-gen", TOY_GEN)):
        with open(os.path.join(root, "benchmark", name + ".json"), "w") as f:
            json.dump(data, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "toy-olmoe-serve", "source": "none", "reduced": [],
        "why": "toy", "file": "benchmark/configs/toy-olmoe-serve.json"})
    bench["workloads"].append({
        "name": "toy-moe-gen", "config": "toy-olmoe-serve",
        "traffic": "toy-gen", "chips": 1, "why": "toy"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "serve-moe-gen" in m.get("workloads", ()):
            m["workloads"].append("toy-moe-gen")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def test_toy_olmoe_rehearses_the_cells_runner(tmp_path):
    """The OLMoE block through ``serve_backlog`` on the CPU: the float32
    reference calls the bf16 engine's tokens correct, prefix reuse and
    full slots as in the cell; and the
    uniform formula of ``decode_step_bytes`` (the share of experts that n
    live tokens reach) against the program's own count of the experts it
    touched. Uniform choice reaches the most experts there are to reach,
    so the formula bounds the count from above; at this size the routing
    of seeded random weights is not uniform (the busiest expert carries
    2.0-2.2 times the mean load, against 1.5 for uniform choice of 12
    among 8) and the count reads 6.0-6.1 against the formula's 6.78 at
    four live slots, 7.1-7.2 against 7.81 at eight (two seeds each): 8 to
    11% under, not the 5% the issue hoped for. What the count reads at
    the published widths is the chip's to say (PERF.md, PR 28)."""
    root = make_toy_moe(str(tmp_path))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""),
               JAX_COMPILATION_CACHE_DIR=os.path.join(root, ".jax_cache"))
    names = ["experts_touched_mean", "expert_load_max_over_mean",
             "decode_active_share",
             "engine_host_share", "expert_ffn_share"]
    r = subprocess.run(
        [sys.executable, "-c", DRIVER, "3", json.dumps(names)],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
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
    # one decode window, so at most the short "drain" chunk is left to
    # compile in the window (it cannot be warmed on purpose and the ramp
    # usually meets it; tests/bench/test_bench_harness.py says when)
    assert rehearsal["metrics"]["compiles_in_window"]["value"] <= 1.0
    assert rehearsal["metrics"]["prefix_hit_share"]["value"] > 30.0
    values = got["values"]
    assert values["expert_ffn_share"] is None        # no device trace
    for name in names[:-1]:
        assert values[name] is not None, (name, values)
    assert 1.0 <= values["expert_load_max_over_mean"] <= 8 / 3 + 1e-6
    # the formula at the slice's own mean of live slots
    live = values["decode_active_share"] / 100.0 * 4
    formula = 8 * family.experts_touched_share(TOY_OLMOE, live)
    assert formula == pytest.approx(6.779, abs=0.001)      # all four live
    assert 0.85 * formula <= values["experts_touched_mean"] \
        <= 1.01 * formula
