"""The tenth family of the benchmark, ``lfm2_moe`` (PR 67): its file keeps
to the families' API and off the program; the configuration
``lfm2-8b-a1b-d14`` is the published one but for its depth, with the
counts its ``reduced_why`` and ``deployment`` state; the reference check
is sized to the engine and runs through a restored state; the cell
``serve-extract-gen`` keeps the contract and its traffic is the issue's;
the three readers this PR brings read a built trace and built spans, and
find nothing, without an error, in a program that lacks what they read.
On the CPU, no chip, no weight at the published widths."""

import ast
import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_pins  # noqa: E402
import bench_toy  # noqa: E402

from benchmark import (harness, program_scopes, program_spans,  # noqa: E402
                       serving, systems)
from benchmark.families import lfm2_moe as family  # noqa: E402

ROOT = bench_toy.REPO
CELL = "serve-extract-gen"
CONFIG_NAME = "lfm2-8b-a1b-d14"
# the entries that were there and this cell reports, each with a cell
# that reported it before
TWINS = {name: "serve-doc" for name in (
    "prefix_hit_share", "decode_roofline", "device_idle_share",
    "peak_hbm_gb", "decode_program_step_ms", "prefill_program_share",
    "engine_host_share", "decode_active_share", "paged_attn_roofline",
    "decode_delivered_share", "decode_overrun_share", "prefill_fill_share",
    "prefill_ms_per_ktoken", "prefill_attn_share", "prefill_dense_share",
    "unscoped_share")}
TWINS.update({name: "serve-moe-gen" for name in (
    "expert_ffn_share", "experts_touched_mean", "expert_load_max_over_mean",
    "prefill_routed_share", "prefill_combine_share")})
TWINS["prefill_expert_share"] = "serve-reason-gen"
READERS = ("conv_mixer_share", "state_snapshot_share", "state_restore_share")
PUBLISHED_TYPES = (["conv", "conv"] + ["full_attention", "conv", "conv",
                                       "conv"] * 4
                   + ["full_attention", "conv", "conv"] * 2)
# the catalog row's ``config`` (architectures.jsonl, LFM2-8B-A1B)
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168, "layer_types": PUBLISHED_TYPES,
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 24, "num_key_value_heads": 8,
    "rope_theta": 1000000, "routed_scaling_factor": 1,
    "use_expert_bias": True, "vocab_size": 65536}
REDUCED = ["num_hidden_layers", "layer_types"]


def cell_config() -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs",
                           CONFIG_NAME + ".json")) as f:
        return json.load(f)


# -- the family's file -------------------------------------------------------

def test_the_family_passes_the_api_check_and_keeps_off_the_program():
    assert systems.family({"family": "lfm2_moe"}) is family
    path = os.path.join(ROOT, "benchmark", "families", "lfm2_moe.py")
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
    # a state with no [H, P, N] matrix offers the ``ssm_*`` readers nothing
    assert not hasattr(family, "ssm_op")
    # a departure for each thing assumed, each named in the file
    for name in ("order", "conv_act", "qk_norm", "norm_place", "scores",
                 "bias", "weights", "experts", "dense_layers"):
        assert f"``{name}=" in family.__doc__


def test_the_configuration_is_the_published_one_but_for_its_depth():
    import jax

    config = cell_config()
    assert config["source"] == (
        "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json")
    differs = [k for k, v in PUBLISHED.items() if config.get(k, "") != v]
    assert sorted(differs) == sorted(REDUCED) and config["reduced"] == REDUCED
    assert config["reduced_from"]["num_hidden_layers"] == 24
    # the first fourteen: both dense layers, then three whole periods
    assert config["layer_types"] == PUBLISHED_TYPES[:14]
    assert config["num_hidden_layers"] == 14
    assert family.layer_counts(config) == (11, 3, 2)
    assert family.layer_counts(PUBLISHED) == (18, 6, 2)
    assert set(config["assumed"]) >= {
        "head_dim", "tie_word_embeddings", "qk_norm", "experts", "router",
        "conv", "torch_dtype", "state_dtype", "init"}
    for key in ("reduced_why", "deployment"):
        assert len(config[key]) > 200
    assert "3.67" in config["reduced_why"]
    assert "4,667,077,376" in config["reduced_why"] and "9.33 GB" in config[
        "reduced_why"]
    system = config["system"]
    assert {k: system[k] for k in ("max_batch", "max_len", "page_size",
                                   "kv_dtype", "prefix_cache")} == {
        "max_batch": 64, "max_len": 4608, "page_size": 128,
        "kv_dtype": "bf16", "prefix_cache": True}
    assert str(system["num_pages"]) in config["deployment"].replace(",", "")
    cfg = family.model_config(config)
    assert (cfg.n_layers, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.conv_taps, cfg.d_ff, cfg.n_dense_layers, cfg.d_expert,
            cfg.n_experts, cfg.top_k, cfg.tie_embeddings) == (
        14, 32, 8, 64, 3, 7168, 2, 1792, 32, 4, True)
    assert cfg.state_chunk == system["page_size"]
    shapes = jax.eval_shape(lambda: family.init_params(
        cfg, jax.random.key(0)))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == \
        family.total_params(config)
    with pytest.raises(ValueError, match="published LFM2-MoE layers"):
        family.model_config(dict(config, conv_bias=True))


def test_the_reference_check_is_sized_to_the_engine_and_restores_a_state():
    """Two prompts of 3,600 tokens that share 3,328: the second reuses 26
    pages and begins from the state the 26th keeps, so the comparison
    that decides ``correct`` runs through a restored state; both fit a
    slot with their answers, in the bucket the traffic's cold prompts
    take."""
    config = cell_config()
    system, check = config["system"], config["system"]["reference_check"]
    assert check == {"prompt_tokens": 3600, "shared_tokens": 3328,
                     "new_tokens": 32}
    page = system["page_size"]
    assert check["shared_tokens"] % page == 0
    assert check["shared_tokens"] // page == 26
    assert check["prompt_tokens"] + check["new_tokens"] < system["max_len"]
    assert 2048 < check["prompt_tokens"] <= 4096
    traffic = harness.load_cell(CELL)[3]
    first = np.arange(1, check["prompt_tokens"] + 1, dtype=np.int32)
    second = first.copy()
    second[check["shared_tokens"]:] += 7
    prefill, _ = serving.warm_cells(
        [(first, 32), (second, 32)], system, traffic["prefill_limits"])
    # the first cold in the 4,096 bucket, the second a 512-token suffix
    # behind 26 pages
    assert (1, 4096, 32) in prefill and (1, 512, 32) in prefill


def test_counts_at_the_published_widths():
    m = cell_config()
    assert family.conv_params(m) == 16_785_408       # its norm among them
    assert family.attention_params(m) == 10_487_936
    assert family.dense_params(m) == 44_040_192
    assert family.expert_params(m) == 11_010_048
    assert family.router_params(m) == 65_568
    assert family.total_params(m) == 4_667_077_376
    assert 2 * family.total_params(m) / 1e9 == pytest.approx(9.33, abs=5e-3)
    whole = family.total_params(PUBLISHED)
    assert 8.3e9 < whole < 8.4e9                     # "8.3B", tied
    assert family.total_params(dict(
        PUBLISHED, tie_word_embeddings=False)) - whole == 65536 * 2048
    assert family.state_bytes_per_slot_layer(m) == 8192
    assert family.kv_bytes_per_token_layer(m) == 2048
    # a page of 128 tokens: K and V of three layers, and the eleven tails
    assert 128 * 3 * family.kv_bytes_per_token_layer(m) == 786_432
    assert 11 * family.state_bytes_per_slot_layer(m) == 90_112


def test_decode_step_bytes_at_the_cells_sizes():
    m = cell_config()
    full = {"occupancy_samples": [64] * 4, "live_kv_tokens_mean": 64 * 3900.0}
    total = family.decode_step_bytes(m, full)
    kv = family.attention_kv_bytes(m, full)
    tails = family.conv_state_bytes(m, full)
    assert kv == pytest.approx(64 * 3900 * 6144)                # 1.53 GB
    assert tails == pytest.approx(2 * 11 * 64 * 8192)           # 11.5 MB
    experts = 2.0 * 12 * 32 * family.expert_params(m)           # 8.46 GB
    head = 2.0 * 2048 * 65536                                   # 0.27 GB
    # 64 tokens of 4 choices in 32 reach every expert
    assert family.experts_touched_share(m, 64) == pytest.approx(1.0, abs=1e-3)
    assert total == pytest.approx(
        2.0 * (family.total_params(m) - 12 * family.router_params(m))
        + 4.0 * 12 * family.router_params(m) + kv + tails
        - 2.0 * 12 * 32 * family.expert_params(m)
        * (1 - family.experts_touched_share(m, 64)), rel=1e-3)
    assert 0.76 < experts / total < 0.80 and 0.13 < kv / total < 0.15
    assert 0.02 < head / total < 0.03
    assert family.attention_kv_bytes(m, {}) == 0.0
    # the grouped kernel's two calls a layer: gate and up, then down
    up = family.grouped_expert_cost(m, 1792, 4 * 4096.0)
    down = family.grouped_expert_cost(m, 2048, 4 * 4096.0)
    assert up["flops"] == 2 * down["flops"] == 2.0 * 16384 * 2048 * 1792 * 2
    assert family.grouped_expert_cost(m, 7168, 10.0) is None
    is_expert = family.expert_ffn_op(m)
    assert is_expert("%f.1 = f32[32,64,1792]{2,1,0} fusion(bf16[64,2048] %h, "
                     "bf16[3,32,2048,1792] %wi_up)")
    assert is_expert("%f.2 = f32[64,32]{1,0} fusion(f32[3,2048,32] %router)")
    assert not is_expert("%f.3 = f32[64,7168]{1,0} fusion(bf16[64,2048] %h, "
                         "bf16[2,2048,7168] %w_up)")
    assert not is_expert("%f.4 = f32[64,1,6144]{2,1,0} fusion(bf16[64,1,2048]"
                         " %u, bf16[3,2048,6144] %in_proj)")


# -- the three new readers, on a built trace and built spans ------------------

MAPS = [
    {"program": "jit_paged_decode_c16_w36", "scopes": {
        "c.1": ["f32[64,1,6144]", "short_conv"],
        "e.1": ["f32[32,64,1792]", "moe_experts"],
        "a.1": ["bf16[64,32,128]", "attn"],
        "u.1": ["f32[64,2048]", ""]}},
    {"program": "jit_paged_prefill_w32", "scopes": {
        "c.1": ["f32[2,4096,6144]", "short_conv"],
        "s.1": ["bf16[11,3072,2,2048]", "state_snapshot"],
        "e.1": ["f32[32768,1792]", "moe_experts"],
        "u.1": ["f32[2,4096,2048]", ""]}}]


class _Trace:
    """Eight runs of one decode program (200 ms: 30 under ``short_conv``,
    120 under ``moe_experts``, 40 under ``attn``, 10 unnamed) and eight
    of one prefill program (400 ms: 60 under ``short_conv``, 8 under
    ``state_snapshot``, 320 under ``moe_experts``, 12 unnamed)."""

    def __init__(self, unnamed=10.0):
        kinds = (
            ("jit_paged_decode_c16_w36(7)", (
                ("%c.1 = f32[64,1,6144]{2,1,0} fusion()", 30.0),
                ("%e.1 = f32[32,64,1792]{2,1,0} fusion()", 120.0),
                ("%a.1 = bf16[64,32,128]{2,1,0} custom-call()", 40.0),
                ("%u.1 = f32[64,2048]{1,0} fusion()", unnamed))),
            ("jit_paged_prefill_w32(9)", (
                ("%c.1 = f32[2,4096,6144]{2,1,0} fusion()", 60.0),
                ("%s.1 = bf16[11,3072,2,2048]{3,2,1,0} scatter()", 8.0),
                ("%e.1 = f32[32768,1792]{1,0} custom-call()", 320.0),
                ("%u.1 = f32[2,4096,2048]{2,1,0} fusion()", 12.0))))
        modules, ops = [], []
        for run in range(8):
            for at, (name, parts) in enumerate(kinds):
                t = run * 1.0 + at * 0.5
                modules.append((name, t, t + sum(ms for _, ms in parts) * 1e-3))
                for op, ms in parts:
                    ops.append((op, t, t + ms * 1e-3))
                    t += ms * 1e-3
        self.devices = [{"modules": modules, "ops": ops}]


def _run(trace):
    return type("Run", (), {
        "trace": trace, "config": cell_config(),
        "device": {"kind": "TPU v5 lite", "platform": "tpu"},
        "counters": {}})


def _dispatch(group, restores=None, **more):
    attrs = dict(group=group, bucket=256, new_tokens=100 * group, **more)
    if restores is not None:
        attrs.update(state_restores=restores, state_snapshot_pages=0)
    return {"name": "engine.dispatch_prefill", "span_id": id(attrs),
            "attrs": attrs}


def test_the_two_readers_by_scope_read_the_programs_own_names(monkeypatch):
    monkeypatch.setattr(program_scopes, "scope_maps", lambda: MAPS)
    program_scopes.summary.cache_clear()
    run = _run(_Trace())
    assert harness.load_reader("conv_mixer_share")(run) == pytest.approx(15.0)
    assert harness.load_reader("state_snapshot_share")(run) == \
        pytest.approx(2.0)
    # over a tenth of the decode runs' time unnamed: the maps are another
    # tree's, and the decode share is withheld; the prefill's stands
    program_scopes.summary.cache_clear()
    holed = _run(_Trace(unnamed=40.0))
    assert harness.load_reader("conv_mixer_share")(holed) is None
    assert harness.load_reader("state_snapshot_share")(holed) == \
        pytest.approx(2.0)
    # a program whose maps name neither scope (the parent's, another
    # family's): zero of a joined, named run, not an error
    bare = [dict(m, scopes={k: [v[0], "ssm_mixer" if v[1] in (
        "short_conv", "state_snapshot") else v[1]]
        for k, v in m["scopes"].items()}) for m in MAPS]
    monkeypatch.setattr(program_scopes, "scope_maps", lambda: bare)
    program_scopes.summary.cache_clear()
    assert harness.load_reader("conv_mixer_share")(_run(_Trace())) == 0.0
    assert harness.load_reader("state_snapshot_share")(_run(_Trace())) == 0.0
    # no map (a program from before the maps, or a CPU rehearsal), no
    # trace: nothing, and no error
    monkeypatch.setattr(program_scopes, "scope_maps", lambda: None)
    program_scopes.summary.cache_clear()
    for name in READERS[:2]:
        assert harness.load_reader(name)(_run(_Trace())) is None
        assert harness.load_reader(name)(_run(None)) is None
    program_scopes.summary.cache_clear()


def test_the_restore_share_counts_rows_begun_from_a_pages_state(monkeypatch):
    read = harness.load_reader("state_restore_share")
    spans = ([_dispatch(2, 2), _dispatch(1, 1), _dispatch(2, 1),
              _dispatch(1, 0), _dispatch(2, 2)]
             + [{"name": "engine.dispatch_decode", "attrs": {"live": 64}}])
    monkeypatch.setattr(program_spans, "engine_spans", lambda: spans)
    assert read(_run(None)) == pytest.approx(100.0 * 6 / 8)
    # fewer dispatches than a median needs; a program that counts no
    # restores (the parent's, a plan whose pages keep no state); none
    monkeypatch.setattr(program_spans, "engine_spans", lambda: spans[:3])
    assert read(_run(None)) is None
    monkeypatch.setattr(program_spans, "engine_spans",
                        lambda: [_dispatch(2) for _ in range(8)])
    assert read(_run(None)) is None
    monkeypatch.setattr(program_spans, "engine_spans", lambda: None)
    assert read(_run(None)) is None


# -- the entries, by name -----------------------------------------------------

def test_the_cells_entries_keep_the_contract(bench):
    cell = bench_pins.cell_entry(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG_NAME, "extract-backlog-shared", 1)
    assert len(cell["why"]) <= 200
    assert sum(c["chips"] == 4 for c in bench["workloads"]) == 1
    entry = bench_pins.config_entry(bench, cell["config"])
    config = cell_config()
    bench_pins.check_reduced(entry, config, PUBLISHED)
    assert sorted(entry["reduced"]) == sorted(REDUCED)
    assert config["name"] == entry["name"] and len(entry["why"]) <= 200
    assert set(bench_pins.reported(bench, CELL, "end_to_end")) == {
        "serve_tokens_per_s", "setup_s"}
    moved = bench_pins.entry(bench["end_to_end"], "serve_tokens_per_s")
    assert CELL in moved["workloads"] and moved["bound"] == 0.045
    mine = bench_pins.reports(bench, CELL, tuple(TWINS),
                              moves="serve_tokens_per_s")
    for stem, m in mine.items():     # one entry, shared with a cell before
        assert TWINS[stem] in m["workloads"]
    # the first recurrent cell whose prefix is reused
    assert "prefix_hit_share" in bench_pins.reported(bench, CELL)
    # a state with no [H, P, N] matrix: none of the ``ssm_*`` readers'
    assert not {"ssm_mixer_share", "ssm_state_roofline", "ssm_step_share",
                "prefill_scan_share"} & set(bench_pins.reported(bench, CELL))
    # the three readers this PR brings wait for their entries
    # (``test_bench_harness.py`` holds ``per_layer`` under 64: PERF.md 7)
    assert not set(READERS) & {m["name"] for m in bench["per_layer"]}
    for name in READERS:
        assert harness.load_reader(name) is not None
    assert len(bench["workloads"]) <= 24
    with open(os.path.join(ROOT, "BENCHMARK.json"), "rb") as f:
        assert len(f.read()) <= 64 * 1024


def test_the_traffic_is_one_cold_bucket_and_suffixes_behind_reused_pages():
    bench, cell, config, traffic = harness.load_cell(CELL)
    lengths = {k: (traffic[k]["min"], traffic[k]["max"])
               for k in ("doc_tokens", "question_tokens", "answer_tokens")}
    assert lengths == {"doc_tokens": (3072, 3840),
                       "question_tokens": (32, 128),
                       "answer_tokens": (320, 640)}
    system = config["system"]
    assert sum(hi for _, hi in lengths.values()) == 4608 == system["max_len"]
    assert (traffic["generator"], traffic["runner"]) == (
        "doc_backlog", "serve_backlog")
    assert (traffic["askings"], traffic["docs_per_cycle"],
            traffic["wave_docs"], traffic["ramp_s"], traffic["trace_s"]) == (
        4, 48, 2, 45, 6)
    assert 0 < len(traffic["why"])
    from benchmark.generators import doc_backlog

    def cycle(seed):
        b = doc_backlog.Backlog(traffic, config["vocab_size"], seed)
        return [(r.prompt, r.max_new_tokens, r.session)
                for r in b.first_cycle()]

    first, other = cycle(3), cycle(2 ** 31 + 17)
    assert len(first) == len(other) == 192
    assert [len(p) for p, _, _ in first] != [len(p) for p, _, _ in other]
    # every cold prompt in the 4,096 bucket alone; a slot holds the
    # longest with its answer
    assert all(3104 <= len(p) <= 3968 for p, _, _ in first + other)
    assert all(len(p) + n < system["max_len"] for p, n, _ in first + other)
    assert np.mean([n for _, n, _ in first]) == pytest.approx(480, abs=2)
    # each context asked four times: 24-30 whole pages of it are reused
    page = system["page_size"]
    docs = {}
    for p, _, session in first:
        docs.setdefault(session, []).append(p)
    assert all(len(v) == 4 for v in docs.values()) and len(docs) == 48

    def shared(a, b):
        n = min(len(a), len(b))
        return int(np.argmin(np.append(a[:n] == b[:n], False)))

    reused = [shared(v[0], other) // page for v in docs.values()
              for other in v[1:]]
    assert len(reused) == 144 and 24 <= min(reused) <= max(reused) <= 30
    # the warm-up's grid: the 4,096 bucket at 32 pages, alone and in
    # pairs, the suffixes' buckets behind the cached pages; the decode
    # programs at the table's full width (36 pages a slot)
    prefill, decode = serving.warm_cells(
        [(p, n) for p, n, _ in first], system, traffic["prefill_limits"])
    assert {(1, 4096, 32), (2, 4096, 32)} <= prefill
    assert {n for n, _, _ in prefill} == {1, 2}
    assert {t for _, t, _ in prefill} <= {32, 64, 128, 256, 4096}
    # (a slot whose prompt and answer end inside 31 pages reserves 32;
    # with 64 slots one that reserves more is always alive)
    assert decode == {32, 36}
    # the pool holds what 64 slots reserve (29-36 pages and the overshoot
    # page each) and the idle contexts the next askings hit
    assert system["num_pages"] >= system["max_batch"] * 37


# -- the cell's runner, rehearsed at toy size --------------------------------

sys.path.insert(0, os.path.join(ROOT, "tests"))

TOY_GEN = {
    "generator": "doc_backlog", "runner": "serve_backlog",
    "doc_tokens": {"dist": "uniform", "min": 34, "max": 46},
    "question_tokens": {"dist": "uniform", "min": 4, "max": 14},
    "answer_tokens": {"dist": "uniform", "min": 20, "max": 40},
    "askings": 4, "docs_per_cycle": 4, "wave_docs": 2, "max_waiting": 2,
    "ramp_s": 0.5, "trace_s": 3, "prefill_limits": bench_toy.LIMITS}
DRIVER = '''
import json, sys
sys.path.insert(0, ".")
from benchmark import harness, program_spans
rc = harness.main(["--workload", "toy-extract-gen", "--seed", sys.argv[1],
                   "--seconds", "4", "--trace", "1", "--rehearse"])
run = type("Run", (), {"trace": None, "counters": {},
                       "config": harness.load_cell("toy-extract-gen")[2]})
values = {name: harness.load_reader(name)(run) for name in json.loads(
    sys.argv[2])}
print("inside " + json.dumps({"rc": rc, "values": values}))
'''


def make_toy_extract(tmp: str) -> str:
    """The toy copy with the CPU tests' toy LFM2 configuration cut to a
    dense convolution, attention and a routed convolution, in bf16, a toy
    mix of ``extract-backlog-shared``'s shape (a context of two whole
    pages and more asked four times: a cold prompt in the 64 bucket, then
    suffixes behind two reused pages) and their cell, added as files and
    entries; the cell reports what ``serve-extract-gen`` reports."""
    import test_lfm2_moe as toy

    root = bench_toy.make_toy(tmp)
    config = dict(toy.CONFIG, name="toy-lfm2-serve", family="lfm2_moe",
                  layer_types=["conv", "full_attention", "conv"],
                  num_hidden_layers=3, num_dense_layers=1,
                  source="none: a toy for the CPU tests", reduced=[],
                  torch_dtype="bfloat16", system={
                      "max_batch": 4, "max_len": 128, "page_size": 16,
                      "num_pages": 40, "kv_dtype": "bf16",
                      "prefix_cache": True,
                      "reference_check": {"prompt_tokens": 60,
                                          "shared_tokens": 32,
                                          "new_tokens": 6}})
    for name, data in (("configs/toy-lfm2-serve", config),
                       ("traffic/toy-extract", TOY_GEN)):
        with open(os.path.join(root, "benchmark", name + ".json"), "w") as f:
            json.dump(data, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "toy-lfm2-serve", "source": "none", "reduced": [],
        "why": "toy", "file": "benchmark/configs/toy-lfm2-serve.json"})
    bench["workloads"].append({
        "name": "toy-extract-gen", "config": "toy-lfm2-serve",
        "traffic": "toy-extract", "chips": 1, "why": "toy"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("toy-extract-gen")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def test_toy_lfm2_rehearses_the_cells_runner(tmp_path):
    """Three layers through ``serve_backlog`` on the CPU, in bf16 as the
    cell serves it, prefix cache ON as the cell has it: the warm-up's
    cached cells hit the pages they want, the reference check's second
    prompt begins from the state its second page keeps and the float32
    reference calls the engine's tokens correct, the window's askings
    reuse their contexts' pages (the one counter a rehearsal prints), and
    the spans carry the restores the new reader counts."""
    root = make_toy_extract(str(tmp_path))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""),
               JAX_COMPILATION_CACHE_DIR=os.path.join(root, ".jax_cache"))
    names = ["state_restore_share", "experts_touched_mean",
             "decode_active_share", "conv_mixer_share",
             "state_snapshot_share", "paged_attn_roofline"]
    import subprocess

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
    said = next(line for line in lines if line.startswith("bench reference:"))
    assert " reference_prefix_hit_pages=" in said
    warmed = next(line for line in lines if line.startswith("bench programs:"))
    hit, wanted = (int(warmed.split(f" {k}=")[1].split()[0]) for k in (
        "cached_pages_hit", "cached_pages_wanted"))
    assert hit == wanted > 0
    # a rehearsal prints counters only: the pages the askings reused
    assert set(rehearsal["metrics"]) == {"compiles_in_window",
                                         "prefix_hit_share"}
    assert rehearsal["metrics"]["prefix_hit_share"]["value"] > 30.0
    values = got["values"]
    # most rows begin from a page's state (three askings in four)
    assert 40.0 < values["state_restore_share"] <= 100.0
    assert 0.0 < values["experts_touched_mean"] <= 8.0
    assert all(values[n] is None for n in names[3:])    # no device trace
