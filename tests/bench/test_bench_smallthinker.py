"""The ``smallthinker`` model family of the benchmark (PR 51): its file
passes the family contract, the configuration is the published one but for
its depth (the published keys written HERE, not read from a catalog outside
the repo), its counts are pinned at the published widths, the new reader
reads synthetic spans, each departure of its reference alone makes the
comparison that decides ``correct`` fail, and a toy configuration of it
rehearses ``serve-brief-gen``'s runner on the CPU, in a temporary copy to
which the toy is added as new files and entries."""

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

from benchmark import harness, reference, serving, systems  # noqa: E402
from benchmark.families import smallthinker as family  # noqa: E402

ROOT = bench_toy.REPO
# what every backlog cell reports, what every cell of routed experts, and
# what a cell over a plan with a window
BRIEF_TWINS = ("decode_program_step_ms", "decode_roofline",
               "prefill_program_share", "prefix_hit_share",
               "device_idle_share", "peak_hbm_gb", "engine_host_share",
               "decode_active_share", "decode_delivered_share",
               "decode_overrun_share", "prefill_fill_share",
               "expert_ffn_share", "experts_touched_mean",
               "expert_load_max_over_mean", "prefill_expert_share",
               "kv_window_read_share", "paged_attn_roofline")
PERIOD = [0, 1, 1, 1]
# https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/blob/main/
# config.json, the keys that say something about the model's shape
PUBLISHED = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_layout": PERIOD * 13, "rope_scaling": None, "rope_theta": 1500000,
    "sliding_window_layout": PERIOD * 13, "sliding_window_size": 4096,
    "tie_word_embeddings": False, "vocab_size": 151936}


def cell_config() -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "smallthinker-21ba3b-instruct-d8.json")) as f:
        return json.load(f)


def cell_traffic() -> dict:
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "brief-backlog-report.json")) as f:
        return json.load(f)


# -- the family's file -------------------------------------------------------

def test_the_family_passes_the_api_check_and_keeps_off_the_program():
    assert systems.family({"family": "smallthinker"}) is family
    path = os.path.join(ROOT, "benchmark", "families", "smallthinker.py")
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


def test_the_configuration_is_the_published_one_but_for_its_depth():
    config = cell_config()
    assert config["source"] == (
        "https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/"
        "blob/main/config.json")
    differs = [k for k, v in PUBLISHED.items() if config.get(k) != v]
    # depth is the ONLY cut: both per-layer lists stay whole, and the
    # first ``num_hidden_layers`` entries of each are the layers run
    assert differs == ["num_hidden_layers"] == config["reduced"]
    assert config["reduced_from"] == {"num_hidden_layers": 52}
    assert config["num_hidden_layers"] == 8
    assert family._layouts(config) == ((0, 1, 1, 1) * 2,) * 2
    assert family.attention_layer_counts(config) == (2, 6)
    assert {"router_input", "gate_act", "rope", "window", "router",
            "torch_dtype", "init"} <= set(config["assumed"])
    assert "pipeline" in config["deployment"]
    cfg = family.model_config(config)
    assert (cfg.n_layers, cfg.n_experts, cfg.top_k, cfg.window) == (
        8, 64, 6, 4096)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_expert) == (
        28, 4, 128, 768)
    system = config["system"]
    assert system["max_batch"] == 32 and system["max_len"] == 8192
    assert system["num_pages"] >= 2048 and system["page_size"] == 128
    assert system["prefix_cache"] is True
    # live arrays: the weights and the pool, at least 12 GB of the chip's 16
    live = 2 * family.total_params(config) + system["num_pages"] * 128 * (
        8 * family.kv_bytes_per_token_layer(config))
    assert 12e9 <= live < 13.5e9
    check = system["reference_check"]
    assert check == {"prompt_tokens": 4600, "shared_tokens": 4224,
                     "new_tokens": 32}
    assert check["shared_tokens"] > 4096 and check["shared_tokens"] % 128 == 0


def test_counts_at_the_published_widths():
    m = cell_config()
    assert family.expert_params(m) == 3 * 2560 * 768              # 5.90M
    assert family.attention_params(m) == 2560 * 4608 + 3584 * 2560
    assert family.router_params(m) == 2560 * 64
    assert family.kv_bytes_per_token_layer(m) == 2048
    assert family.total_params(m) == 3_966_937_600
    # the uncut model: 52 layers
    assert family.total_params(PUBLISHED) == pytest.approx(21.5e9, rel=0.01)
    assert family.attention_layer_counts(PUBLISHED) == (13, 39)
    cost = family.grouped_expert_cost(m, 768, 6 * 8192)
    assert cost["flops"] == 2.0 * 6 * 8192 * 2560 * 768 * 2
    assert cost["bytes"] == pytest.approx(64 * 2 * 2560 * 768 * 2, rel=1e-6)
    assert family.grouped_expert_cost(m, 2560, 6 * 8192)["flops"] == \
        2.0 * 6 * 8192 * 768 * 2560
    assert family.grouped_expert_cost(m, 1024, 6 * 8192) is None


def test_decode_step_bytes_at_the_cells_sizes():
    """32 full slots of 6,400 tokens: 1.11 GB of attention and head
    weights and routers, 6.04 GB of experts of which an even routing's
    step touches 95.7%, 0.84 GB of keys and values in the two full layers
    and 1.61 GB in the six sliding ones (32 x 4,096 tokens each)."""
    m = cell_config()
    counters = {"occupancy_samples": [32] * 5,
                "live_kv_tokens_mean": 32 * 6400.0}
    touched = 64 * (1.0 - (1.0 - 6 / 64) ** 32)
    assert family.experts_touched(m, counters) == pytest.approx(touched)
    assert touched == pytest.approx(61.26, abs=0.01)
    always = 2.0 * (8 * 20_971_520 + 2560 * 151_936) + 4.0 * 8 * 2560 * 64
    experts = 2.0 * 8 * 5_898_240
    kv = 2048 * (2 * 32 * 6400.0 + 6 * 32 * 4096)
    assert always == pytest.approx(1.119e9, rel=0.001)
    assert experts * 64 == pytest.approx(6.040e9, rel=0.001)
    assert kv == pytest.approx(2.450e9, rel=0.001)
    assert family.attention_kv_bytes(m, counters) == pytest.approx(kv)
    assert family.decode_step_bytes(m, counters) == pytest.approx(
        always + experts * touched + kv)
    # the program's own count of the experts a step's rows reach, where a
    # run's counters hold it
    counted = dict(counters, experts_touched_mean=40.0)
    assert family.decode_step_bytes(m, counted) == pytest.approx(
        always + experts * 40.0 + kv)
    # contexts shorter than the window: every layer reads all of them
    short = dict(counters, live_kv_tokens_mean=32 * 300.0)
    assert family.attention_kv_bytes(m, short) == pytest.approx(
        2048 * 8 * 32 * 300.0)
    assert family.decode_step_bytes(m, {}) == pytest.approx(always)


EXPERT_OPS = (
    "%fusion.248 = f32[64,32,768]{2,1,0} fusion(bf16[3,64,2560,768] %w, "
    "s32[] %layer, bf16[32,2560] %h), kind=kOutput",
    "%fusion.2 = f32[32,2560]{1,0} fusion(bf16[64,32,768] %h, "
    "bf16[1,64,768,2560] %w), kind=kOutput",
    "%fusion.3 = f32[32,64]{1,0} fusion(f32[32,2560] %x, "
    "f32[3,2560,64] %router), kind=kOutput",
    '%grouped_expert_ffn.1 = bf16[49152,768]{1,0} custom-call(s32[1] %l), '
    'custom_call_target="tpu_custom_call"')
OTHER_OPS = (
    "%fusion.7 = f32[32,151936]{1,0} fusion(bf16[32,2560] %x, "
    "bf16[2560,151936] %head), kind=kOutput",
    "%fusion.11 = bf16[32,1,4608]{2,1,0} fusion(bf16[32,2560] %h, "
    "bf16[3,2560,4608] %wqkv), kind=kOutput",
    "%fusion.12 = bf16[32,2560]{1,0} fusion(bf16[32,3584] %a, "
    "bf16[3,3584,2560] %wo), kind=kOutput")


def test_the_routed_experts_operations_are_told_by_their_shapes():
    is_expert_op = family.expert_ffn_op(cell_config())
    assert all(is_expert_op(n) for n in EXPERT_OPS)
    assert not any(is_expert_op(n) for n in OTHER_OPS)


# -- the new reader, on synthetic spans ---------------------------------------

def dispatch_span(i, **attrs):
    return {"name": "engine.dispatch_decode", "span_id": f"d{i}",
            "parent_id": "it", "duration": 0.001,
            "attrs": dict(slots=32, **attrs)}


def test_window_bound_share_reads_the_programs_own_counts(monkeypatch):
    from benchmark import program_spans

    spans = [dispatch_span(i, live=32, slots_past_window=32 - (i == 0),
                           kv_rows_full=32 * 6000, kv_rows_window=32 * 4096)
             for i in range(6)]
    spans.append(dispatch_span(9, live=32))      # a program with no window
    monkeypatch.setattr(program_spans, "engine_spans", lambda: spans)
    run = type("Run", (), {"trace": None, "config": cell_config()})
    read = harness.load_reader("window_bound_share")
    assert read(run) == pytest.approx(100.0 * (6 * 32 - 1) / (6 * 32))
    # this family's two full and six sliding layers
    assert harness.load_reader("kv_window_read_share")(run) == \
        pytest.approx(100.0 * (2 * 6000 + 6 * 4096) / (8 * 6000))
    # too few dispatches, a program that counts none (the parent's, or a
    # plan with no window), no spans: nothing, and no error
    monkeypatch.setattr(program_spans, "engine_spans", lambda: spans[:3])
    assert read(run) is None
    monkeypatch.setattr(program_spans, "engine_spans", lambda: spans[-1:] * 8)
    assert read(run) is None
    monkeypatch.setattr(program_spans, "engine_spans", lambda: None)
    assert read(run) is None


# -- the entries --------------------------------------------------------------

def test_the_cells_entries_keep_the_contract(bench):
    """The entries of this cell, each found by its NAME with the cell
    under its ``workloads``: what a later PR appends behind them, or adds
    to the cell, turns nothing here."""
    cell = bench_pins.cell_entry(bench, "serve-brief-gen")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "smallthinker-21ba3b-instruct-d8", "brief-backlog-report", 1)
    entry = bench_pins.config_entry(bench, cell["config"])
    config = cell_config()
    bench_pins.check_reduced(entry, config, PUBLISHED)
    assert set(bench_pins.reported(bench, "serve-brief-gen",
                                   "end_to_end")) == {
        "serve_tokens_per_s", "setup_s"}
    moved = bench_pins.entry(bench["end_to_end"], "serve_tokens_per_s")
    assert "serve-brief-gen" in moved["workloads"] and moved["bound"] == 0.045
    mine = bench_pins.reports(bench, "serve-brief-gen",
                              BRIEF_TWINS + ("window_bound_share",),
                              moves="serve_tokens_per_s")
    own = mine["window_bound_share"]
    assert (own["source"], own["layer"], own["unit"], own["better"]) == (
        "program_span", "engine scheduler", "%", "higher")
    assert own["workloads"] == ["serve-brief-gen"]
    for stem in BRIEF_TWINS:        # one entry, shared with the cells before
        assert len(mine[stem]["workloads"]) > 1
    assert "compiles_in_window" in bench_pins.reported(bench,
                                                       "serve-brief-gen")
    # a share of a roofline the cell's family cannot count stays unlisted
    assert "grouped_expert_ffn_roofline" not in {
        m["name"] for m in bench["per_layer"]}
    traffic = cell_traffic()
    lengths = {k: (traffic[k]["min"], traffic[k]["max"])
               for k in ("doc_tokens", "question_tokens", "answer_tokens")}
    assert lengths == {"doc_tokens": (5120, 6912),
                       "question_tokens": (32, 128),
                       "answer_tokens": (320, 640)}
    assert sum(hi for _, hi in lengths.values()) == 7680        # 60 pages
    # every prompt is past the window before its first decode step
    assert lengths["doc_tokens"][0] + lengths["question_tokens"][0] > \
        config["sliding_window_size"]
    assert (traffic["generator"], traffic["runner"]) == (
        "doc_backlog", "serve_backlog")
    assert traffic["askings"] == 4
    assert traffic["docs_per_cycle"] % traffic["wave_docs"] == 0
    assert traffic["prefill_limits"] == {"max_group": 1,
                                         "max_score_elements": 67108864}
    # every slot's largest reservation fits the pool at once
    system = config["system"]
    assert system["max_batch"] * (7680 // 128 + 1) <= system["num_pages"]
    # the warm-up's grid holds the cold document, the questions behind a
    # cached one and the check's two prompts, all at the 64-page table
    check = system["reference_check"]
    rng = np.random.default_rng(0)
    document = rng.integers(1, 151936, 6912, dtype=np.int32)
    samples = [(np.concatenate([document, rng.integers(
                    1, 151936, n, dtype=np.int32)]), 640) for n in (128, 32)]
    samples.append((rng.integers(1, 151936, check["prompt_tokens"],
                                 dtype=np.int32), 32))
    prefill, decode = serving.warm_cells(samples, system,
                                         traffic["prefill_limits"])
    assert (1, 8192, 64) in prefill and decode == {64}
    assert {t for _, t, _ in prefill} <= {32, 64, 128, 256, 512, 8192}
    assert all(n == 1 and wp == 64 for n, _, wp in prefill)


# -- each departure alone fails the comparison that decides ``correct`` ------

sys.path.insert(0, os.path.join(ROOT, "tests"))


@pytest.fixture(scope="module")
def served():
    """Two prompts through the toy engine, as ``serving.prepare_engine``
    serves its reference check: both past the window, the second reusing
    the first's pages."""
    import test_smallthinker as toy

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
    None, {"router_input": "post_attention"}, {"gate_act": "silu"},
    {"rope": "everywhere"}, {"window": None}],
    ids=["published", "router_input", "gate_act", "rope", "window"])
def test_each_departure_alone_reads_not_correct(served, departure):
    config, params, out = served

    def logits(*args):
        return family.logits(*args, **(departure or {}))

    gap = max(reference.token_gap(logits, config, params, prompt, tokens)[0]
              for prompt, tokens in out)
    if departure is None:
        assert gap <= serving.TOKEN_GAP_TOL
    else:
        assert gap > 2 * serving.TOKEN_GAP_TOL


# -- the cell's runner, rehearsed at toy size --------------------------------

TOY_GEN = {
    "generator": "doc_backlog", "runner": "serve_backlog",
    "doc_tokens": {"dist": "uniform", "min": 80, "max": 108},
    "question_tokens": {"dist": "uniform", "min": 4, "max": 12},
    "answer_tokens": {"dist": "uniform", "min": 20, "max": 40},
    "askings": 4, "docs_per_cycle": 4, "wave_docs": 2, "max_waiting": 1,
    "ramp_s": 1, "trace_s": 4, "prefill_limits": bench_toy.LIMITS}
DRIVER = '''
import json, sys
sys.path.insert(0, ".")
from benchmark import harness
rc = harness.main(["--workload", "toy-brief-gen", "--seed", sys.argv[1],
                   "--seconds", "5", "--trace", "1", "--rehearse"])
run = type("Run", (), {"trace": None, "counters": {},
                       "config": harness.load_cell("toy-brief-gen")[2]})
values = {name: harness.load_reader(name)(run) for name in json.loads(
    sys.argv[2])}
print("inside " + json.dumps({"rc": rc, "values": values}))
'''


def make_toy_smallthinker(tmp: str) -> str:
    """The toy copy with the CPU tests' toy SmallThinker configuration
    (window 8 over pages of 16), a toy mix of ``brief-backlog-report``'s
    shape (every prompt ten windows long, short answers, one decode table)
    and their cell, added as files and entries; the cell reports what
    ``serve-brief-gen`` reports."""
    import test_smallthinker as toy

    root = bench_toy.make_toy(tmp)
    config = dict(toy.CONFIG, name="toy-smallthinker-serve",
                  source="none: a toy for the CPU tests", reduced=[],
                  torch_dtype="bfloat16", system={
                      "max_batch": 4, "max_len": 256, "page_size": 16,
                      "num_pages": 68, "kv_dtype": "bf16",
                      "prefix_cache": True,
                      "reference_check": {"prompt_tokens": 90,
                                          "shared_tokens": 64,
                                          "new_tokens": 6}})
    for name, data in (("configs/toy-smallthinker-serve", config),
                       ("traffic/toy-brief", TOY_GEN)):
        with open(os.path.join(root, "benchmark", name + ".json"), "w") as f:
            json.dump(data, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "toy-smallthinker-serve", "source": "none", "reduced": [],
        "why": "toy", "file": "benchmark/configs/toy-smallthinker-serve.json"})
    bench["workloads"].append({
        "name": "toy-brief-gen", "config": "toy-smallthinker-serve",
        "traffic": "toy-brief", "chips": 1, "why": "toy"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "serve-brief-gen" in m.get("workloads", ()):
            m["workloads"].append("toy-brief-gen")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def test_toy_smallthinker_rehearses_the_cells_runner(tmp_path):
    """The SmallThinker stack through ``serve_backlog`` on the CPU, in bf16
    as the cell serves it: the float32 reference calls the engine's tokens
    correct (prompts past the window, prefix reuse, full slots), and the
    program's own counts reach the readers: every live slot-step lies past
    the window, and the six sliding layers read a window of contexts ten
    windows long."""
    root = make_toy_smallthinker(str(tmp_path))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""),
               JAX_COMPILATION_CACHE_DIR=os.path.join(root, ".jax_cache"))
    names = ["window_bound_share", "kv_window_read_share",
             "experts_touched_mean", "expert_load_max_over_mean",
             "decode_active_share", "paged_attn_roofline",
             "expert_ffn_share"]
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
    assert values["window_bound_share"] == pytest.approx(100.0)
    # contexts of 84-160 tokens against a window of 8 in 6 of 8 layers
    assert 25.0 < values["kv_window_read_share"] < 35.0
    assert 1.0 <= values["experts_touched_mean"] <= 8.0
