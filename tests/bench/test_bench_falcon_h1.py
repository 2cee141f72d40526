"""The ``falcon_h1`` model family of the benchmark (PR 36): its file passes
the family contract and keeps off the program, the configuration is the
catalog row but for depth (the published keys written HERE, not read from a
catalog outside the repo), its counts are pinned at the published widths,
its three new readers read synthetic traces, the cell's entries keep the
contract (found BY NAME, so that a later cell's entries turn nothing
here), each departure of its reference alone makes the comparison that
decides ``correct`` fail, and a toy configuration of it rehearses
``serve-instruct-gen``'s runner on the CPU, in a temporary copy to which
the toy is added as new files and entries."""

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
from benchmark.families import falcon_h1 as family  # noqa: E402
from benchmark.trace import Trace  # noqa: E402

ROOT = bench_toy.REPO
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
CELL, CONFIG_NAME, TRAFFIC = ("serve-instruct-gen",
                              "falcon-h1-34b-instruct-d4",
                              "instruct-backlog-fewshot")
TWINS = ("decode_program_step_ms", "decode_roofline",
         "prefill_program_share", "device_idle_share",
         "peak_hbm_gb", "engine_host_share", "decode_active_share",
         "decode_delivered_share", "decode_overrun_share",
         "prefill_fill_share", "paged_attn_roofline")
OWN = {"ssm_mixer_share": "lower", "ssm_state_roofline": "higher",
       "prefill_scan_share": "lower"}
# https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct/blob/main/config.json
# as the catalog beside the model-configs guide has it: every key of the
# row's ``config``
PUBLISHED = {
    "attention_bias": False, "attention_in_multiplier": 1,
    "attention_out_multiplier": 0.0375, "attn_layer_indices": None,
    "embedding_multiplier": 5.656854249492381, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 5120, "intermediate_size": 21504,
    "key_multiplier": 0.011048543456039804,
    "lm_head_multiplier": 0.0078125, "mamba_chunk_size": 128,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 128,
    "mamba_d_ssm": 4096, "mamba_d_state": 256, "mamba_expand": 2,
    "mamba_n_groups": 2, "mamba_n_heads": 32,
    "mamba_norm_before_gate": False, "mamba_proj_bias": False,
    "mamba_rms_norm": True, "mamba_use_mlp": True,
    "max_position_embeddings": 262144, "mlp_bias": False,
    "mlp_expansion_factor": 8,
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
    "model_type": "falcon_h1", "num_attention_heads": 20,
    "num_hidden_layers": 72, "num_key_value_heads": 4,
    "num_logits_to_keep": 1, "projectors_bias": False,
    "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 100000000000, "ssm_in_multiplier": 0.25,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                        0.3535533905932738],
    "ssm_out_multiplier": 0.08838834764831845,
    "tie_word_embeddings": False, "vocab_size": 261120}


def cell_config() -> dict:
    with open(os.path.join(
            ROOT, "benchmark/configs", CONFIG_NAME + ".json")) as f:
        return json.load(f)


def benchmark_json() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


# -- the family's file -------------------------------------------------------

def test_the_family_passes_the_api_check_and_keeps_off_the_program():
    assert systems.family({"family": "falcon_h1"}) is family
    path = os.path.join(ROOT, "benchmark", "families", "falcon_h1.py")
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


def test_the_reference_computes_the_recurrence_token_by_token():
    """Not the chunked form the program uses: one ``lax.scan`` step a
    token, over arrays laid out with the sequence axis first."""
    path = os.path.join(ROOT, "benchmark", "families", "falcon_h1.py")
    with open(path) as f:
        source = f.read()
    mixer = source[source.index("def _mixer("):source.index("def _mlp(")]
    assert "jax.lax.scan(\n        token," in mixer
    assert "jnp.moveaxis(v, 1, 0)" in mixer
    assert "cumsum" not in source and "chunk" not in mixer


def test_the_configuration_is_the_catalog_row_but_for_depth():
    import jax

    config = cell_config()
    assert config["source"] == ("https://huggingface.co/tiiuae/"
                                "Falcon-H1-34B-Instruct/blob/main/config.json")
    differs = [k for k, v in PUBLISHED.items() if config.get(k, "absent") != v]
    assert differs == ["num_hidden_layers"] == config["reduced"]
    bench_pins.check_reduced(bench_pins.config_entry(
        benchmark_json(), CONFIG_NAME), config)
    assert config["num_hidden_layers"] == 4       # the guide's floor
    assert config["reduced_from"] == {"num_hidden_layers": 72}
    assert {"state_dtype", "torch_dtype", "init"} <= set(config["assumed"])
    assert "pipeline" in config["reduced_why"]
    assert "pipeline" in config["deployment"]
    assert config["family"] == "falcon_h1"
    # the program's weights are the family's count, leaf for leaf
    cfg = family.model_config(config)
    assert (cfg.n_layers, cfg.d_model, cfg.d_ff) == (4, 5120, 21504)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups,
            cfg.ssm_conv, cfg.ssm_chunk) == (32, 128, 256, 2, 4, 128)
    shapes = jax.eval_shape(lambda k: family.init_params(cfg, k),
                            jax.random.key(0))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == \
        family.total_params(config) == 4_394_354_048
    assert shapes["blocks"]["in_proj"].shape == (4, 5120, 9248)
    assert shapes["blocks"]["wqkv"].shape == (4, 5120, 3584)
    system = config["system"]
    assert system["prefix_cache"] is False
    assert (system["max_batch"], system["max_len"], system["page_size"],
            system["num_pages"]) == (128, 1024, 128, 1280)
    # every slot's largest reservation at once, and spare for deferred frees
    assert system["max_batch"] * 8 < system["num_pages"]
    assert system["reference_check"] == {
        "prompt_tokens": 600, "shared_tokens": 0, "new_tokens": 32}
    # a model that departs from the published block is refused, not guessed
    with pytest.raises(ValueError, match="published Falcon-H1 block"):
        family.model_config(dict(config, mamba_norm_before_gate=True))


def test_counts_at_the_published_widths():
    m = cell_config()
    assert family.attention_params(m) == 5120 * 3584 + 2560 * 5120  # 31.46M
    assert family.mlp_params(m) == 3 * 5120 * 21504                 # 330.30M
    assert family.mixer_params(m) == (5120 * 9248 + 4096 * 5120
                                      + 5120 * 4 + 5120 + 96 + 4096)
    assert family.block_params(m) == pytest.approx(430.1e6, rel=1e-3)
    assert family.conv_dim(m) == 5120
    assert family.kv_bytes_per_token_layer(m) == 2048
    # a slot's state in a layer: 4.19 MB of float32 state, 30 KB of tail
    assert family.state_bytes_per_slot_layer(m) == \
        4 * 32 * 128 * 256 + 2 * 3 * 5120 == 4_225_024
    # the uncut model
    assert family.total_params(dict(m, num_hidden_layers=72)) == \
        pytest.approx(33.6e9, rel=0.01)


def test_decode_step_bytes_at_the_cells_sizes():
    """128 full slots at 520 tokens: 3.44 GB of block weights and 2.67 GB
    of head, 0.55 GB of keys and values, and 4.33 GB of state read and
    written once: the state is two fifths of a step's bytes."""
    m = cell_config()
    counters = {"occupancy_samples": [128] * 5,
                "live_kv_tokens_mean": 128 * 520.0}
    weights = 2.0 * (4 * family.block_params(m) + 5120 * 261120)
    kv = 2048 * 4 * 128 * 520.0
    state = 2.0 * 4_225_024 * 4 * 128
    assert weights == pytest.approx(6.115e9, rel=1e-3)
    assert kv == pytest.approx(0.545e9, rel=1e-2)
    assert state == pytest.approx(4.326e9, rel=1e-3)
    assert family.attention_kv_bytes(m, counters) == pytest.approx(kv)
    assert family.ssm_state_bytes(m, counters) == pytest.approx(state)
    assert family.decode_step_bytes(m, counters) == pytest.approx(
        weights + kv + state)
    assert 0.38 < state / (weights + kv + state) < 0.41
    assert family.decode_step_bytes(m, {}) == pytest.approx(weights)
    assert family.ssm_state_bytes(m, {"occupancy_samples": [64, 128]}) == \
        pytest.approx(state * 0.75)


# -- the new readers, on synthetic traces ------------------------------------

DECODE = "jit_paged_decode_c8_w8(123)"
PREFILL = "jit_paged_prefill_w4(456)"
STATE_OPS = (
    "%fusion.263 = f32[4,128,32,128,256]{4,3,2,1,0} fusion("
    "f32[4,128,32,128,256] %state, s32[] %layer, f32[128,32,256] %b)",
    "%fusion.12 = f32[128,32,128]{2,1,0} fusion(f32[4,128,32,128,256] "
    "%state, s32[] %layer, f32[128,32,256] %c), kind=kInput",
    "%fusion.5 = bf16[4,128,3,5120]{1,3,2,0} fusion(bf16[4,128,3,5120] "
    "%tail, bf16[128,1,5120] %xbc), kind=kLoop")
MIXER_OPS = (
    "%fusion.6 = f32[128,1,9248]{2,1,0} fusion(bf16[128,1,5120] %u, "
    "bf16[4,5120,9248] %in_proj), kind=kOutput",
    "%fusion.7 = f32[128,5120]{1,0} fusion(bf16[128,4096] %y, "
    "bf16[4,4096,5120] %out_proj), kind=kOutput",
    "%fusion.8 = f32[2,32,128,128]{3,2,1,0} fusion(f32[2,128,32] %cs)",
    "%fusion.14 = bf16[2,512,32,128]{3,2,1,0} fusion(f32[2,512,5120] %conv)")
OTHER_OPS = (
    "%fusion.9 = f32[128,261120]{1,0} fusion(bf16[128,5120] %x, "
    "bf16[5120,261120] %head), kind=kOutput",
    "%fusion.10 = bf16[128,1,3584]{2,1,0} fusion(bf16[128,1,5120] %u, "
    "bf16[4,5120,3584] %wqkv), kind=kOutput",
    "%fusion.11 = bf16[128,1,21504]{2,1,0} fusion(bf16[128,1,5120] %h, "
    "bf16[4,5120,21504] %w_gate), kind=kOutput",
    '%paged_decode_attn.3 = bf16[128,20,128]{2,1,0} custom-call(s32[1] %l), '
    'custom_call_target="tpu_custom_call"',
    "%while.4 = (s32[], f32[4,128,32,128,256]) while(%tuple.3)")


def synthetic_trace(runs: int = 6) -> Trace:
    """``runs`` decode runs of 8 steps in 160 ms (20 ms a step), each
    with 32 layer-steps of: a state update of 2 ms, its output's
    reduction of 1 ms, an input projection of 0.5 ms, the head 1 ms; and
    ``runs`` prefill runs of 40 ms with 10 ms of scan operations."""
    modules, ops = [], []
    for i in range(runs):
        t = 0.25 * i
        modules.append((DECODE, t, t + 0.160))
        for j in range(32):
            at = t + 0.005 * j
            ops.append((STATE_OPS[0], at, at + 0.002))
            ops.append((STATE_OPS[1], at + 0.002, at + 0.003))
            ops.append((MIXER_OPS[0], at + 0.003, at + 0.0035))
            ops.append((OTHER_OPS[0], at + 0.0035, at + 0.0045))
        ops.append((OTHER_OPS[4], t, t + 0.160))      # the loop itself
        p = t + 0.170
        modules.append((PREFILL, p, p + 0.040))
        ops.append((MIXER_OPS[2], p, p + 0.006))
        ops.append((MIXER_OPS[3], p + 0.006, p + 0.010))
        ops.append((OTHER_OPS[2], p + 0.010, p + 0.040))
    return Trace([{"modules": modules, "ops": ops, "async_ops": []}], [],
                 extent_s=0.25 * runs)


def test_the_mixers_operations_are_told_by_their_shapes():
    ops = family.ssm_op(cell_config())
    assert all(ops["state"](n) and ops["mixer"](n) for n in STATE_OPS)
    assert all(ops["mixer"](n) and not ops["state"](n) for n in MIXER_OPS)
    assert not any(ops["mixer"](n) or ops["state"](n)
                   for n in OTHER_OPS[:4])
    # at the CPU tests' tiny widths, where the mixer's width is its own
    tiny = dict(cell_config(), hidden_size=64, mamba_d_ssm=48,
                mamba_n_heads=6, mamba_d_head=8, mamba_d_state=16)
    tiny_ops = family.ssm_op(tiny)
    assert tiny_ops["state"]("%f = f32[2,4,6,8,16]{4,3,2,1,0} fusion()")
    assert tiny_ops["state"]("%f = f32[2,4,3,112]{3,2,1,0} fusion()")
    assert tiny_ops["mixer"]("%f = f32[4,1,166]{2,1,0} fusion()")
    assert not tiny_ops["mixer"]("%f = f32[4,1,224]{2,1,0} fusion()")


def test_the_three_readers_on_a_synthetic_trace():
    m = cell_config()
    counters = {"occupancy_samples": [128] * 5,
                "live_kv_tokens_mean": 128 * 520.0}
    run = type("Run", (), {"trace": synthetic_trace(), "config": m,
                           "counters": counters,
                           "device": {"kind": "TPU v5 lite"}})
    assert inside.decode_program_step_ms(run.trace) == pytest.approx(20.0)
    # of a run's 160 ms: 32 x (2 + 1 + 0.5) ms in the mixer's operations
    assert harness.load_reader("ssm_mixer_share")(run) == \
        pytest.approx(100.0 * 32 * 3.5 / 160.0)
    # a step's 4 layers take 4 x 3 ms on the state; 4.326 GB at 819 GB/s
    # are 5.28 ms: an update that reads the state twice sits at 44%
    got = harness.load_reader("ssm_state_roofline")(run)
    assert got == pytest.approx(100.0 * 4.326e9 / 819e9 / 12e-3, rel=1e-3)
    assert 40.0 < got < 50.0
    # of a prefill run's 40 ms, 10 in the scan's operations
    assert harness.load_reader("prefill_scan_share")(run) == \
        pytest.approx(25.0)
    names = list(OWN)
    # too few runs, no trace, another family, and a program with no such
    # operation (the parent's, were it to run the cell): nothing, no error
    run.trace = synthetic_trace(inside.MIN_SAMPLES - 1)
    assert [harness.load_reader(n)(run) for n in names] == [None] * 3
    run.trace = None
    assert [harness.load_reader(n)(run) for n in names] == [None] * 3
    run.trace, run.config = synthetic_trace(), {"family": "llama"}
    assert [harness.load_reader(n)(run) for n in names] == [None] * 3
    bare = synthetic_trace()
    bare.devices[0]["ops"] = [op for op in bare.devices[0]["ops"]
                              if op[0] in OTHER_OPS]
    run.trace, run.config = bare, m
    assert harness.load_reader("ssm_state_roofline")(run) is None
    assert harness.load_reader("prefill_scan_share")(run) is None
    assert not harness.load_reader("ssm_mixer_share")(run)


# -- the entries, by name -----------------------------------------------------

def test_the_cells_entries_keep_the_contract(bench):
    """Every clause of ``test_benchmark_json_keeps_the_contract`` for the
    entries of this cell, each found by its NAME with the cell under its
    ``workloads``: a later PR's entries, appended behind these or added to
    the cell, turn nothing here."""
    cell = bench_pins.cell_entry(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG_NAME, TRAFFIC, 1)
    assert "depth cut" in cell["why"]       # the head's share is its doing
    entry = bench_pins.config_entry(bench, CONFIG_NAME)
    assert entry["file"] == f"benchmark/configs/{CONFIG_NAME}.json"
    bench_pins.check_reduced(entry, cell_config(), PUBLISHED)
    assert entry["reduced"] == ["num_hidden_layers"]
    # one four-chip cell as before
    assert [c["name"] for c in bench["workloads"] if c["chips"] == 4] == [
        "train-2k-fsdp4"]
    assert len(bench["workloads"]) >= 7 and len(bench["configs"]) >= 6
    assert set(bench_pins.reported(bench, CELL, "end_to_end")) == {
        "serve_tokens_per_s", "setup_s"}
    moved = bench_pins.entry(bench["end_to_end"], "serve_tokens_per_s")
    assert CELL in moved["workloads"] and moved["bound"] == 0.045
    assert bench["run_seconds"] == 51
    mine = bench_pins.reports(bench, CELL, TWINS + tuple(OWN),
                              moves="serve_tokens_per_s")
    # there is nothing to hit: a recurrent state has no prefix to share
    assert "prefix_hit_share" not in bench_pins.reported(bench, CELL)
    for stem, m in mine.items():
        if stem in OWN:
            assert (m["source"], m["layer"], m["unit"], m["better"]) == (
                "device_trace", "recurrent state", "%", OWN[stem])
        else:                   # one entry, shared with the cell before
            assert "serve-code-gen" in m["workloads"]
    assert "compiles_in_window" in bench_pins.reported(bench, CELL)
    # the roofline and mfu shares of the accepted benchmark that move the
    # cell's end-to-end metric are reported in it
    assert {"decode_roofline", "paged_attn_roofline",
            "ssm_state_roofline"} <= set(mine)


def test_the_traffic_fills_every_slot_at_the_eight_page_table():
    bench, cell, config, traffic = harness.load_cell(CELL)
    lengths = {k: (traffic[k]["min"], traffic[k]["max"])
               for k in ("doc_tokens", "question_tokens", "answer_tokens")}
    assert lengths == {"doc_tokens": (128, 320),
                       "question_tokens": (32, 128),
                       "answer_tokens": (320, 544)}
    assert sum(hi for _, hi in lengths.values()) == 992 < config[
        "system"]["max_len"]
    assert (traffic["generator"], traffic["runner"]) == (
        "doc_backlog", "serve_backlog")
    assert (traffic["askings"], traffic["docs_per_cycle"],
            traffic["wave_docs"], traffic["max_waiting"],
            traffic["ramp_s"]) == (4, 48, 16, 6, 45)
    assert len(traffic["why"]) > 0
    # every seed offers the same grids of lengths (192 requests a cycle):
    # the same answers, the same prompt tokens in all; only which block
    # meets which item and which answer differs
    from benchmark.generators import doc_backlog

    def lengths_of(seed):
        b = doc_backlog.Backlog(traffic, config["vocab_size"], seed)
        return [(len(r.prompt), r.max_new_tokens) for r in b.first_cycle()]

    first, other = lengths_of(3), lengths_of(2 ** 31 + 17)
    assert len(first) == len(other) == 192 and first != other
    assert sorted(n for _, n in first) == sorted(n for _, n in other)
    assert sum(p for p, _ in first) == sum(p for p, _ in other)
    assert max(p + n for p, n in first + other) <= 992
    assert min(p for p, _ in first + other) >= 160
    # one decode table serves the run, and the warm-up's grid holds the
    # prefill programs a hand-over of two prompts can bring
    system = config["system"]
    shapes = [(np.ones(p, np.int32), n) for p, n in first]
    prefill, decode = serving.warm_cells(shapes, system,
                                         traffic["prefill_limits"])
    assert decode == {8}
    assert {(2, 256, 2), (2, 512, 4), (1, 256, 2), (1, 512, 4)} <= prefill


# -- each departure alone fails the comparison that decides ``correct`` ------

sys.path.insert(0, os.path.join(ROOT, "tests"))


@pytest.fixture(scope="module")
def served():
    """Four prompts through the toy engine, one after another as
    ``serving.prepare_engine`` serves its reference check (no prefix is
    shared: there is no reuse over a recurrent plan)."""
    import test_falcon_h1 as toy

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
    None, {"multipliers": "none"}, {"gate_norm": "before"}, {"groups": 1},
    {"conv_bias": False}, {"key_multiplier": 1}],
    ids=["published", "multipliers", "gate_norm", "groups", "conv_bias",
         "key_multiplier"])
def test_each_departure_alone_reads_not_correct(served, departure):
    config, params, out = served

    def logits(*args):
        return family.logits(*args, **(departure or {}))

    gap = max(reference.token_gap(logits, config, params, prompt, tokens)[0]
              for prompt, tokens in out)
    # 64 tokens, as many as the cell's own check teacher-forces: the
    # published reading within the limit, each departure past it with
    # half as much again to spare
    if departure is None:
        assert gap <= serving.TOKEN_GAP_TOL
    else:
        assert gap > 1.5 * serving.TOKEN_GAP_TOL


# -- the cell's runner, rehearsed at toy size --------------------------------

TOY_GEN = {
    "generator": "doc_backlog", "runner": "serve_backlog",
    "doc_tokens": {"dist": "uniform", "min": 32, "max": 80},
    "question_tokens": {"dist": "uniform", "min": 4, "max": 24},
    "answer_tokens": {"dist": "uniform", "min": 40, "max": 100},
    "askings": 4, "docs_per_cycle": 4, "wave_docs": 2, "max_waiting": 3,
    "ramp_s": 1, "trace_s": 4, "prefill_limits": bench_toy.LIMITS}
DRIVER = '''
import json, sys
sys.path.insert(0, ".")
from benchmark import harness, program_spans
rc = harness.main(["--workload", "toy-instruct-gen", "--seed", sys.argv[1],
                   "--seconds", "5", "--trace", "1", "--rehearse"])
run = type("Run", (), {"trace": None, "counters": {},
                       "config": harness.load_cell("toy-instruct-gen")[2]})
values = {name: harness.load_reader(name)(run) for name in json.loads(
    sys.argv[2])}
spans = program_spans.engine_spans() or []
attrs = {}
for s in spans:
    if s["name"] in ("engine.dispatch_prefill", "engine.dispatch_decode"):
        for k in ("state_installs", "scan_chunks", "state_slots",
                  "state_bytes", "group", "live"):
            if k in s["attrs"]:
                attrs.setdefault(s["name"] + "." + k, []).append(
                    s["attrs"][k])
print("inside " + json.dumps({"rc": rc, "values": values, "attrs": attrs}))
'''


def make_toy_falcon(tmp: str) -> str:
    """The toy copy with the CPU tests' toy Falcon-H1 configuration in
    bf16, a toy mix of ``instruct-backlog-fewshot``'s shape and their
    cell, added as files and entries; the cell reports what
    ``serve-instruct-gen`` reports."""
    import test_falcon_h1 as toy

    root = bench_toy.make_toy(tmp)
    config = dict(toy.CONFIG, name="toy-falcon-serve", family="falcon_h1",
                  source="none: a toy for the CPU tests", reduced=[],
                  torch_dtype="bfloat16", mamba_chunk_size=16, system={
                      "max_batch": 4, "max_len": 256, "page_size": 16,
                      "num_pages": 68, "kv_dtype": "bf16",
                      "prefix_cache": False,
                      "reference_check": {"prompt_tokens": 90,
                                          "shared_tokens": 0,
                                          "new_tokens": 6}})
    for name, data in (("configs/toy-falcon-serve", config),
                       ("traffic/toy-instruct", TOY_GEN)):
        with open(os.path.join(root, "benchmark", name + ".json"), "w") as f:
            json.dump(data, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "toy-falcon-serve", "source": "none", "reduced": [],
        "why": "toy", "file": "benchmark/configs/toy-falcon-serve.json"})
    bench["workloads"].append({
        "name": "toy-instruct-gen", "config": "toy-falcon-serve",
        "traffic": "toy-instruct", "chips": 1, "why": "toy"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("toy-instruct-gen")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def test_toy_falcon_rehearses_the_cells_runner(tmp_path):
    """The Falcon-H1 stack through ``serve_backlog`` on the CPU, in bf16
    as the cell serves it, prefix cache off as the cell has it: the
    float32 reference calls the engine's tokens correct with every slot
    retiring and refilling all through the run, the warm-up's requests
    (built to hit a prefix cache) run as the full prompts they are, and
    the dispatch spans carry the state's counts."""
    root = make_toy_falcon(str(tmp_path))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""),
               JAX_COMPILATION_CACHE_DIR=os.path.join(root, ".jax_cache"))
    names = ["decode_active_share",
             "ssm_mixer_share", "ssm_state_roofline",
             "prefill_scan_share", "paged_attn_roofline"]
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
    assert all(values[n] is None for n in names[1:])    # no device trace
    assert values["decode_active_share"] > 50.0
    attrs = got["attrs"]
    # every prefilled row's state is installed, and a decode chunk's
    # state bytes are its live slots' (2 layers of 3,072 + 672 B of
    # bf16-config state: float32 S [6, 8, 16], bf16 tail [3, 112]), twice
    assert attrs["engine.dispatch_prefill.state_installs"] == \
        attrs["engine.dispatch_prefill.group"]
    assert all(c >= g for c, g in zip(
        attrs["engine.dispatch_prefill.scan_chunks"],
        attrs["engine.dispatch_prefill.group"]))
    assert attrs["engine.dispatch_decode.state_slots"] == \
        attrs["engine.dispatch_decode.live"]
    per_slot = 2 * (4 * 6 * 8 * 16 + 2 * 3 * 112)
    assert attrs["engine.dispatch_decode.state_bytes"] == [
        2 * live * per_slot for live in attrs["engine.dispatch_decode.live"]]
