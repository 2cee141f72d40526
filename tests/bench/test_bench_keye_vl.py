"""The ``keye_vl`` model family of the benchmark (PR 60): its file passes
the family contract, the configuration is the published one but for its
depth (the published keys written HERE, not read from a catalog outside
the repo), its counts are pinned at the published widths against hand
arithmetic, the three new readers read synthetic traces, each departure of
its reference alone makes the comparison that decides ``correct`` fail,
and a toy configuration of it rehearses ``serve-longqa-gen``'s runner on
the CPU, in a temporary copy to which the toy is added as new files and
entries."""

import ast
import json
import os
import subprocess
import sys
from functools import partial

os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else it logs to /tmp

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_pins  # noqa: E402
import bench_toy  # noqa: E402

from benchmark import (decode_scopes, harness, program_scopes,  # noqa: E402
                       reference, serving, systems)
from benchmark.families import keye_vl as family  # noqa: E402

ROOT = bench_toy.REPO
# what every backlog cell reports, what every cell of routed experts, what
# a cell whose layers pick their keys, and PR 57's shares by scope
LONGQA_TWINS = (
    "prefix_hit_share", "decode_roofline", "device_idle_share",
    "peak_hbm_gb", "decode_program_step_ms", "prefill_program_share",
    "engine_host_share", "decode_active_share", "expert_ffn_share",
    "experts_touched_mean", "expert_load_max_over_mean",
    "kv_selected_share", "decode_delivered_share", "decode_overrun_share",
    "prefill_fill_share", "prefill_expert_share", "prefill_ms_per_ktoken",
    "prefill_routed_share", "prefill_combine_share", "prefill_attn_share",
    "prefill_dense_share", "unscoped_share")
OWN = ("sparse_attn_share", "sparse_index_share", "sparse_attn_roofline")
# https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/
# config.json, the keys that say something about the language model's shape
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4,
    "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}


def cell_config() -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "keye-vl-2.0-30b-a3b-d6.json")) as f:
        return json.load(f)


def cell_traffic() -> dict:
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "longqa-backlog-context.json")) as f:
        return json.load(f)


# -- the family's file -------------------------------------------------------

def test_the_family_passes_the_api_check_and_keeps_off_the_program():
    assert systems.family({"family": "keye_vl"}) is family
    path = os.path.join(ROOT, "benchmark", "families", "keye_vl.py")
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
    # this cell's attention is not one kernel's: no count under the
    # shared name whose reader times ``paged_decode_attn*`` alone
    assert not hasattr(family, "attention_kv_bytes")


def test_the_configuration_is_the_published_one_but_for_its_depth():
    config = cell_config()
    assert config["source"] == (
        "https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/"
        "config.json")
    differs = [k for k, v in PUBLISHED.items() if config.get(k) != v]
    assert differs == ["num_hidden_layers"] == config["reduced"]
    assert config["reduced_from"] == {"num_hidden_layers": 48}
    assert config["num_hidden_layers"] == 6
    assert {"qk_norm", "indexer", "index_norm", "index_rope", "chunks",
            "mrope", "router", "towers", "torch_dtype", "init"} <= set(
        config["assumed"])
    assert "not run" in config["assumed"]["towers"]
    assert "pipeline" in config["deployment"]
    cfg = family.model_config(config)
    assert (cfg.n_layers, cfg.n_experts, cfg.top_k, cfg.index_topk) == (
        6, 128, 8, 2048)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_expert) == (
        32, 4, 128, 768)
    assert (cfg.index_heads, cfg.index_dim, cfg.mrope_sections) == (
        16, 64, (16, 24, 24))
    assert cfg.vocab_size == 151936 and cfg.d_model == 2048
    system = config["system"]
    assert system["max_batch"] == 32 and system["max_len"] == 8192
    assert 2048 <= system["num_pages"] <= 2560 and system["page_size"] == 128
    assert system["prefix_cache"] is True
    # live arrays: the weights and the pools (K and V of 4 x 128 and the
    # index key's whole lanes a token and layer), 12-13.5 GB of the 16
    live = 2 * family.total_params(config) + system["num_pages"] * 128 * 6 * (
        family.kv_bytes_per_token_layer(config) + 2 * 128)
    assert 12e9 <= live < 13.5e9
    check = system["reference_check"]
    assert check == {"prompt_tokens": 4600, "shared_tokens": 4224,
                     "new_tokens": 32}
    # both prompts past the keys a query keeps, the shared pages too
    assert check["shared_tokens"] > 2048 and check["shared_tokens"] % 128 == 0


def test_counts_at_the_published_widths():
    m = cell_config()
    assert family.attention_params(m) == 2048 * 4096 + 2 * 2048 * 512 \
        + 4096 * 2048 == 18_874_368
    assert family.indexer_params(m) == 2048 * (16 * 64) + 2048 * 64 \
        + 2048 * 16 + 128 == 2_260_992 + 128
    assert family.router_params(m) == 262_144
    assert family.expert_params(m) == 3 * 2048 * 768
    assert 128 * family.expert_params(m) == 603_979_776
    assert family.layer_params(m) == 625_381_760               # 625.4M
    assert family.total_params(m) == 6 * 625_381_760 + 2 * 151_936 * 2048 \
        + 2048 == 4_374_622_464                                # 4.37B
    assert family.kv_bytes_per_token_layer(m) == 2048
    assert family.cache_bytes_per_token(m) == 6 * (2048 + 128) == 13_056
    # the uncut model: 48 layers, 30.6B
    assert family.total_params(PUBLISHED) == pytest.approx(30.64e9, rel=0.001)
    cost = family.grouped_expert_cost(m, 768, 8 * 8192)
    assert cost["flops"] == 2.0 * 8 * 8192 * 2048 * 768 * 2
    assert cost["bytes"] == pytest.approx(128 * 2 * 2048 * 768 * 2, rel=1e-6)
    assert family.grouped_expert_cost(m, 2048, 8 * 8192)["flops"] == \
        2.0 * 8 * 8192 * 768 * 2048
    assert family.grouped_expert_cost(m, 1024, 8 * 8192) is None


def test_decode_step_bytes_at_the_cells_sizes():
    """32 full slots of 7,000 tokens: 0.88 GB of attention, indexer and
    head weights and routers, 7.25 GB of experts of which an even
    routing's step touches 87%, 0.81 GB of selected keys and values (2.75
    GB if every row were read) and 0.17 GB of index keys."""
    m = cell_config()
    counters = {"occupancy_samples": [32] * 5,
                "live_kv_tokens_mean": 32 * 7000.0}
    touched = 128 * (1.0 - (1.0 - 8 / 128) ** 32)
    assert family.experts_touched(m, counters) == pytest.approx(touched)
    assert touched == pytest.approx(111.77, abs=0.01)
    always = 2.0 * (6 * (18_874_368 + 2_261_120) + 2048 * 151_936) \
        + 4.0 * 6 * 262_144
    experts = 2.0 * 6 * 4_718_592
    selected = 6 * 32 * 2048 * 2048.0
    index_keys = 6 * 32 * 7000 * 128.0
    assert always == pytest.approx(0.882e9, rel=0.001)
    assert experts * 128 == pytest.approx(7.248e9, rel=0.001)
    assert selected == pytest.approx(0.805e9, rel=0.001)
    assert index_keys == pytest.approx(0.172e9, rel=0.001)
    assert 6 * 32 * 7000 * 2048.0 == pytest.approx(2.752e9, rel=0.001)
    assert family.sparse_attention_bytes(m, counters) == pytest.approx(
        selected + index_keys)
    assert family.decode_step_bytes(m, counters) == pytest.approx(
        always + experts * touched + selected + index_keys)
    counted = dict(counters, experts_touched_mean=100.0)
    assert family.decode_step_bytes(m, counted) == pytest.approx(
        always + experts * 100.0 + selected + index_keys)
    # contexts under the keys a query keeps: every row is read
    short = dict(counters, live_kv_tokens_mean=32 * 300.0)
    assert family.sparse_attention_bytes(m, short) == pytest.approx(
        6 * 32 * 300.0 * (2048 + 128))
    assert family.decode_step_bytes(m, {}) == pytest.approx(always)


EXPERT_OPS = (
    "%fusion.248 = f32[128,32,768]{2,1,0} fusion(bf16[6,128,2048,768] %w, "
    "s32[] %layer, bf16[32,2048] %h), kind=kOutput",
    "%fusion.2 = f32[32,2048]{1,0} fusion(bf16[128,32,768] %h, "
    "bf16[1,128,768,2048] %w), kind=kOutput",
    "%fusion.3 = f32[32,128]{1,0} fusion(f32[32,2048] %x, "
    "f32[6,2048,128] %router), kind=kOutput",
    '%grouped_expert_ffn.1 = bf16[65536,768]{1,0} custom-call(s32[1] %l), '
    'custom_call_target="tpu_custom_call"')
OTHER_OPS = (
    "%fusion.7 = f32[32,151936]{1,0} fusion(bf16[32,2048] %x, "
    "bf16[2048,151936] %head), kind=kOutput",
    "%fusion.11 = f32[32,1,6224]{2,1,0} fusion(bf16[32,2048] %h, "
    "bf16[6,2048,6224] %w_in), kind=kOutput",
    "%fusion.12 = bf16[32,2048]{1,0} fusion(bf16[32,4096] %a, "
    "bf16[6,4096,2048] %wo), kind=kOutput")


def test_the_routed_experts_operations_are_told_by_their_shapes():
    is_expert_op = family.expert_ffn_op(cell_config())
    assert all(is_expert_op(n) for n in EXPERT_OPS)
    assert not any(is_expert_op(n) for n in OTHER_OPS)


# -- the new readers, on a synthetic trace -----------------------------------

class _Trace:
    """Eight runs of one decode program of 8 steps, 80 ms each: 20 ms
    under ``attn``, 12 under ``index_select``, 40 under ``moe_experts``,
    8 under no name."""
    devices = [{}]

    def __init__(self, unnamed=8.0):
        ops = (("%a.1 = bf16[32,32,128]{2,1,0} custom-call()", 20.0),
               ("%i.1 = f32[32,8192]{1,0} fusion()", 12.0),
               ("%e.1 = f32[128,32,768]{2,1,0} fusion()", 40.0),
               ("%u.1 = f32[32,2048]{1,0} fusion()", unnamed))
        self.modules, self.ops = [], []
        for run in range(8):
            t = run * 0.2
            self.modules.append(("jit_paged_decode_c8_w64(7)", t,
                                 t + sum(ms for _, ms in ops) * 1e-3))
            for name, ms in ops:
                self.ops.append((name, t, t + ms * 1e-3))
                t += ms * 1e-3
        self.devices = [{"modules": self.modules, "ops": self.ops}]

    def module_time(self, match, whole=False):
        runs = [e - s for n, s, e in self.modules if match(n)]
        return sum(runs), len(runs)


MAPS = [{"program": "jit_paged_decode_c8_w64", "scopes": {
    "a.1": ["bf16[32,32,128]", "attn"],
    "i.1": ["f32[32,8192]", "index_select"],
    "e.1": ["f32[128,32,768]", "moe_experts"],
    "u.1": ["f32[32,2048]", ""]}}]


def _run(trace):
    return type("Run", (), {
        "trace": trace, "config": cell_config(),
        "device": {"kind": "TPU v5 lite", "platform": "tpu"},
        "counters": {"occupancy_samples": [32] * 5,
                     "live_kv_tokens_mean": 32 * 7000.0}})


def test_the_sparse_readers_read_the_decode_programs_by_scope(monkeypatch):
    monkeypatch.setattr(program_scopes, "scope_maps", lambda: MAPS)
    program_scopes.summary.cache_clear()
    run = _run(_Trace())
    assert decode_scopes.decode_share(run.trace, ("moe_experts",)) == \
        pytest.approx(50.0)
    assert harness.load_reader("sparse_attn_share")(run) == \
        pytest.approx(40.0)                         # 32 of 80 ms
    assert harness.load_reader("sparse_index_share")(run) == \
        pytest.approx(37.5)                         # 12 of 32 ms
    # 0.977 GB over 819 GB/s is 1.19 ms; the step's attention and
    # selection took 32 ms / 8 steps = 4 ms
    want = 100.0 * family.sparse_attention_bytes(
        run.config, run.counters) / 819e9 / 4e-3
    assert harness.load_reader("sparse_attn_roofline")(run) == \
        pytest.approx(want)
    assert 29.0 < want < 30.5
    # over a tenth of the decode runs' time unnamed: the maps are another
    # tree's, and no share is given
    program_scopes.summary.cache_clear()
    holed = _run(_Trace(unnamed=30.0))
    for name in OWN:
        assert harness.load_reader(name)(holed) is None
    # no map (the parent's program, or a CPU rehearsal): nothing, no error
    monkeypatch.setattr(program_scopes, "scope_maps", lambda: None)
    program_scopes.summary.cache_clear()
    for name in OWN:
        assert harness.load_reader(name)(_run(_Trace())) is None
        assert harness.load_reader(name)(_run(None)) is None
    program_scopes.summary.cache_clear()


def test_a_family_without_the_count_reads_no_roofline(monkeypatch):
    monkeypatch.setattr(program_scopes, "scope_maps", lambda: MAPS)
    program_scopes.summary.cache_clear()
    run = _run(_Trace())
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "smallthinker-21ba3b-instruct-d8.json")) as f:
        run.config = json.load(f)
    assert harness.load_reader("sparse_attn_roofline")(run) is None
    program_scopes.summary.cache_clear()


# -- the entries --------------------------------------------------------------

def test_the_cells_entries_keep_the_contract(bench):
    """The entries of this cell, each found by its NAME with the cell
    under its ``workloads``: what a later PR appends behind them, or adds
    to the cell, turns nothing here."""
    cell = bench_pins.cell_entry(bench, "serve-longqa-gen")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "keye-vl-2.0-30b-a3b-d6", "longqa-backlog-context", 1)
    entry = bench_pins.config_entry(bench, cell["config"])
    config = cell_config()
    bench_pins.check_reduced(entry, config, PUBLISHED)
    assert set(bench_pins.reported(bench, "serve-longqa-gen",
                                   "end_to_end")) == {
        "serve_tokens_per_s", "setup_s"}
    moved = bench_pins.entry(bench["end_to_end"], "serve_tokens_per_s")
    assert "serve-longqa-gen" in moved["workloads"] and \
        moved["bound"] == 0.045
    mine = bench_pins.reports(bench, "serve-longqa-gen", LONGQA_TWINS + OWN,
                              moves="serve_tokens_per_s")
    for name in OWN:
        own = mine[name]
        assert (own["source"], own["layer"], own["unit"]) == (
            "device_trace", "kernels", "%")
        assert own["workloads"] == ["serve-longqa-gen"]
    assert [mine[name]["better"] for name in OWN] == [
        "lower", "lower", "higher"]
    for stem in LONGQA_TWINS:       # one entry, shared with the cells before
        assert len(mine[stem]["workloads"]) > 1
    reported = bench_pins.reported(bench, "serve-longqa-gen")
    assert "compiles_in_window" in reported
    # the time of ``paged_decode_attn*`` against a family's K/V bytes is
    # not this cell's: its selection's reads lie outside those instructions
    assert "paged_attn_roofline" not in reported
    assert "latent_attn_share" not in reported
    traffic = cell_traffic()
    lengths = {k: (traffic[k]["min"], traffic[k]["max"])
               for k in ("doc_tokens", "question_tokens", "answer_tokens")}
    assert lengths == {"doc_tokens": (6144, 7296),
                       "question_tokens": (32, 128),
                       "answer_tokens": (320, 640)}
    assert sum(hi for _, hi in lengths.values()) == 8064        # 63 pages
    # every decode step stands at three times the keys a query keeps
    assert lengths["doc_tokens"][0] + lengths["question_tokens"][0] > \
        3 * config["sa_config"]["topk"]
    assert (traffic["generator"], traffic["runner"]) == (
        "doc_backlog", "serve_backlog")
    assert traffic["askings"] == 4 and traffic["max_waiting"] == 1
    assert traffic["docs_per_cycle"] == 48 and traffic["trace_s"] == 6
    assert traffic["wave_docs"] in (1, 2) and 45 <= traffic["ramp_s"] <= 60
    assert traffic["docs_per_cycle"] % traffic["wave_docs"] == 0
    assert traffic["prefill_limits"] == {"max_group": 1,
                                         "max_score_elements": 67108864}
    # every slot's largest reservation fits the pool at once
    system = config["system"]
    assert system["max_batch"] * (8064 // 128 + 1) <= system["num_pages"]
    # the warm-up's grid holds the cold document, the questions behind a
    # cached one and the check's two prompts, all at the 64-page table
    check = system["reference_check"]
    rng = np.random.default_rng(0)
    document = rng.integers(1, 151936, 7296, dtype=np.int32)
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
    serves its reference check: both past the keys a query keeps, the
    second reusing the first's pages (K and V pages in float32:
    ``tests/test_keye_vl.py`` says why)."""
    import jax.numpy as jnp
    import test_keye_vl as toy

    from ray_tpu.serve.paged_llm import PagedLLMEngine

    cfg = family.model_config(toy.CONFIG)
    params = toy.make_params(cfg)
    rng = np.random.default_rng(3)
    first = rng.integers(1, 128, 50, dtype=np.int32)
    second = np.concatenate([first[:32],
                             rng.integers(1, 128, 19, dtype=np.int32)])
    eng = PagedLLMEngine(cfg=cfg, params=params, max_batch=2, max_len=128,
                         page_size=8, num_pages=40)
    pools = eng._programs.pools
    pools[:2] = [pool.astype(jnp.float32) for pool in pools[:2]]
    eng.start()
    out = [(p, serving.collect(eng, eng.submit(p, max_new_tokens=12)))
           for p in (first, second)]
    eng.stop()
    return toy.CONFIG, params, out


@pytest.mark.parametrize("departure", [
    None, {"qk_norm": "none"}, {"indexer": "none"}, {"topk": 4},
    {"index_norm": "none"}, {"index_rope": "none"},
    {"norm_topk_prob": False}],
    ids=["published", "qk_norm", "indexer", "topk", "index_norm",
         "index_rope", "norm_topk_prob"])
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
    "doc_tokens": {"dist": "uniform", "min": 96, "max": 114},
    "question_tokens": {"dist": "uniform", "min": 4, "max": 12},
    "answer_tokens": {"dist": "uniform", "min": 20, "max": 40},
    "askings": 4, "docs_per_cycle": 4, "wave_docs": 2, "max_waiting": 1,
    "ramp_s": 1, "trace_s": 4, "prefill_limits": bench_toy.LIMITS}
DRIVER = '''
import json, sys
sys.path.insert(0, ".")
from benchmark import harness
rc = harness.main(["--workload", "toy-longqa-gen", "--seed", sys.argv[1],
                   "--seconds", "5", "--trace", "1", "--rehearse"])
run = type("Run", (), {"trace": None, "counters": {},
                       "config": harness.load_cell("toy-longqa-gen")[2]})
values = {name: harness.load_reader(name)(run) for name in json.loads(
    sys.argv[2])}
print("inside " + json.dumps({"rc": rc, "values": values}))
'''


def make_toy_keye(tmp: str) -> str:
    """The toy copy with the CPU tests' toy Keye-VL configuration (8 keys
    kept, pages of 16), a toy mix of ``longqa-backlog-context``'s shape
    (every prompt twelve times the keys a query keeps, short answers, one
    decode table) and their cell, added as files and entries; the cell
    reports what ``serve-longqa-gen`` reports."""
    import test_keye_vl as toy

    root = bench_toy.make_toy(tmp)
    config = dict(toy.CONFIG, name="toy-keye-serve",
                  source="none: a toy for the CPU tests", reduced=[],
                  torch_dtype="bfloat16", system={
                      "max_batch": 4, "max_len": 256, "page_size": 16,
                      "num_pages": 68, "kv_dtype": "bf16",
                      "prefix_cache": True,
                      "reference_check": {"prompt_tokens": 90,
                                          "shared_tokens": 64,
                                          "new_tokens": 6}})
    # at 8 keys of a hundred one key tipped by a bf16 rounding is an
    # eighth of a layer's attention (tests/test_keye_vl.py): the toy keeps
    # 64, as the cell keeps 2,048 of 7,000
    config["sa_config"] = dict(config["sa_config"], topk=64)
    for name, data in (("configs/toy-keye-serve", config),
                       ("traffic/toy-longqa", TOY_GEN)):
        with open(os.path.join(root, "benchmark", name + ".json"), "w") as f:
            json.dump(data, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "toy-keye-serve", "source": "none", "reduced": [],
        "why": "toy", "file": "benchmark/configs/toy-keye-serve.json"})
    bench["workloads"].append({
        "name": "toy-longqa-gen", "config": "toy-keye-serve",
        "traffic": "toy-longqa", "chips": 1, "why": "toy"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "serve-longqa-gen" in m.get("workloads", ()):
            m["workloads"].append("toy-longqa-gen")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def test_toy_keye_rehearses_the_cells_runner(tmp_path):
    """The Keye-VL stack through ``serve_backlog`` on the CPU, in bf16 as
    the cell serves it: the float32 reference calls the engine's tokens
    correct (prompts past the keys a query keeps, prefix reuse, full
    slots), and the program's own counts reach the readers: of the rows
    the live contexts hold, the share a layer's selection keeps. (Seed 4:
    at hidden 64 in bf16 one key of 64 tipped across the selection's
    boundary can turn a token: seed 3 reads 0.24 on one token of twelve,
    seeds 4 and 5 read 0.0. The chip's seeds at the published widths are
    in PERF.md.)"""
    root = make_toy_keye(str(tmp_path))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""),
               JAX_COMPILATION_CACHE_DIR=os.path.join(root, ".jax_cache"))
    names = ["kv_selected_share", "experts_touched_mean",
             "expert_load_max_over_mean", "decode_active_share",
             "sparse_attn_share", "sparse_attn_roofline", "expert_ffn_share"]
    r = subprocess.run(
        [sys.executable, "-c", DRIVER, "4", json.dumps(names)],
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
    for name in names[-3:]:
        assert values[name] is None, name           # no device trace
    for name in names[:-3]:
        assert values[name] is not None, (name, values)
    # contexts of 100-166 tokens against 64 keys kept
    assert 35.0 < values["kv_selected_share"] < 65.0
    assert 1.0 <= values["experts_touched_mean"] <= 8.0


# -- the cell's programs, compiled for a v5e that is described ---------------

HBM = 15.75e9       # what the compiler gives a v5e chip's programs


@pytest.fixture(scope="module")
def v5e():
    """One described v5e chip (several workers import this module; only
    the one given it may load the TPU's library: the call is here, in a
    fixture, and nowhere at import)."""
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler, or it is taken
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: the next run would warn
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices[0]
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(device, program, dims):
    """The cell's decode program (``dims``: chunk, table pages) or
    prefill program (prompts, tokens, table pages) at the configuration's
    widths over its pools, compiled for ``device``. It reaches the
    engine's programs by their signatures, which the benchmark itself
    does not: where a later PR changes one, the test skips."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.models import keye_vl
    from ray_tpu.serve import engine_programs

    one_chip = SingleDeviceSharding(device)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    config = cell_config()
    cfg, system = family.model_config(config), config["system"]
    params = jax.tree.map(
        lambda a: shape(a.shape, a.dtype),
        jax.eval_shape(partial(keye_vl.init_params, cfg), jax.random.key(0)))
    layers, pages, slots = (cfg.n_layers, system["num_pages"],
                            system["max_batch"])
    twin = shape((layers, pages, 128, cfg.n_kv_heads, cfg.head_dim),
                 jnp.bfloat16)
    scale = shape((layers, 1, 1, 1), jnp.float32)
    pools = (twin, twin, scale, scale,
             shape((layers, pages, 128, 128), jnp.bfloat16))
    key = jax.eval_shape(lambda: jax.random.key(0))
    try:
        if program == "decode":
            chunk, table = dims
            fn = partial(engine_programs._paged_decode_impl, cfg,
                         chunk=chunk, page_size=128, quantized=False)
            args = (shape((slots, table), jnp.int32),
                    shape((slots,), jnp.int32), shape((slots,), jnp.int32),
                    shape((slots,), jnp.bool_), shape((slots,), jnp.float32),
                    key)
        else:
            n, tokens, table = dims
            fn = partial(engine_programs._paged_prefill_impl, cfg,
                         page_size=128, quantized=False)
            args = (shape((n, table), jnp.int32),
                    shape((n, tokens), jnp.int32), shape((n,), jnp.int32),
                    shape((n,), jnp.int32), shape((n,), jnp.float32), key)
        lowered = jax.jit(fn, donate_argnums=tuple(range(1, 6))).lower(
            params, *pools, *args)
    except (AttributeError, TypeError) as e:
        pytest.skip(f"the engine's programs are reached otherwise now: {e}")
    return lowered.compile()


def test_the_decode_program_compiles_with_both_kernels(v5e):
    """``serve-longqa-gen``'s decode program (chunk 8, the 64-page table)
    at the published widths: the chip's compiler takes the decode kernel
    with a selection and the index kernel over 64-wide keys in rows of
    128, no operation copies a table's worth of index keys out of the
    pool (``bf16[2048,128,128]``: the gathered formulation's), and the
    program fits beside its arguments (12.83 GB: 8.75 of weights, 4.08 of
    pools)."""
    compiled = _compile(v5e, "decode", (8, 64))
    text = compiled.as_text()
    assert "paged_decode_attn" in text and "index_decode_scores" in text
    assert "bf16[2048,128,128]" not in text
    m = compiled.memory_analysis()
    assert 12.7e9 < m.argument_size_in_bytes < 12.95e9
    assert m.temp_size_in_bytes < 0.3e9
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < HBM


@pytest.mark.parametrize("dims,kernel,temp", [
    ((1, 8192, 64), True, 1.5e9), ((1, 128, 64), False, 0.3e9)],
    ids=["cold-document", "question"])
def test_the_prefill_programs_compile(v5e, dims, kernel, temp):
    """The cold document's prefill (one row of 8,192 tokens over the
    64-page table) holds the prefill kernel with flags and the grouped
    expert kernel and fits the chip beside the engine's arrays (its index
    scores go in blocks of 2,048 queries under ``SCORES_MAX_BYTES``); a
    question of up to 128 tokens behind a cached document is under the
    kernel's rule and holds the plain formulation."""
    compiled = _compile(v5e, "prefill", dims)
    text = compiled.as_text()
    assert ("paged_prefill_attn" in text) is kernel
    if kernel:
        assert "grouped_expert_ffn" in text
    m = compiled.memory_analysis()
    assert m.temp_size_in_bytes < temp
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < HBM
