"""A toy copy of the benchmark for the CPU tests: the repo's ``benchmark``
directory and ``BENCHMARK.json`` copied into a temporary directory, with a
configuration, its model family, three traffic mixes, three cells and a
per-layer metric ADDED as new files and new entries (no file that is there is edited)."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TOY_MODEL = {
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 512, "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": False, "torch_dtype": "bfloat16",
    "family": "toy_family", "source": "none: a toy for the CPU tests",
    "reduced": []}
TOY_ENGINE = dict(TOY_MODEL, name="toy-serve", system={
    "max_batch": 4, "max_len": 256, "page_size": 16,
    "num_pages": 68, "kv_dtype": "bf16", "prefix_cache": True,
    "reference_check": {"prompt_tokens": 90, "shared_tokens": 64,
                        "new_tokens": 6}})
TOY_TRAINER = dict(TOY_MODEL, name="toy-train", system={
    "mesh_axes": {"dp": 1}, "strategy": "dp",
    "remat": "none", "fused_loss": True, "warmup_steps": 1,
    "batch_sequences": 2})
TOY_TRAINER4 = dict(TOY_MODEL, name="toy-train4", system={
    "mesh_axes": {"fsdp": 4}, "strategy": "fsdp",
    "remat": "none", "fused_loss": True, "warmup_steps": 1,
    "batch_sequences": 4})
LIMITS = {"max_group": 2, "max_score_elements": 1 << 30}
TOY_CHAT = {
    "generator": "chat_sessions", "runner": "serve_open_loop",
    "sessions_per_s": 1.5, "template_seed": 7,
    "apps": {"dist": "zipf", "n": 2, "s": 1.0}, "system_prompt_tokens": 32,
    "turns": {"dist": "uniform", "min": 0.5, "max": 3.5},
    "user_tokens": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                    "min": 4, "max": 40},
    "answer_tokens": {"dist": "lognormal", "median": 10, "sigma": 0.5,
                      "min": 4, "max": 24},
    "gap_s": {"dist": "lognormal", "median": 0.5, "sigma": 0.3,
              "min": 0.2, "max": 1.0},
    "max_context_tokens": 224, "nominal_ttft_s": 0.05,
    "nominal_tpot_s": 0.01, "ramp_s": 1, "tail_s": 2, "cap_s": 30,
    "trace_s": 1, "prefill_limits": LIMITS}
TOY_DOC = {
    "generator": "doc_backlog", "runner": "serve_backlog",
    "doc_tokens": {"dist": "uniform", "min": 64, "max": 128},
    "question_tokens": {"dist": "uniform", "min": 4, "max": 12},
    "answer_tokens": {"dist": "uniform", "min": 4, "max": 12},
    "askings": 3, "docs_per_cycle": 4, "wave_docs": 2, "max_waiting": 2,
    "ramp_s": 1, "trace_s": 1, "prefill_limits": LIMITS}
TOY_JOB = {"generator": "train_job", "runner": "train", "seq_len": 64,
           "trace_s": 1}
DUMMY_FAMILY = '''"""A model family added by a later PR: a file of its own."""

from benchmark.families.llama import init_params, model_config  # noqa: F401
'''
DUMMY_READER = '''"""A per-layer metric added by a later PR: a file of its own."""


def read(run):
    return float(len(run.counters.get("occupancy_samples", [])))
'''


def make_toy(tmp: str) -> str:
    """Copy the benchmark and add the toy cells beside what is there."""
    root = os.path.join(tmp, "checkout")
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    before = _digest(root)
    for name, data in (("configs/toy-serve", TOY_ENGINE),
                       ("configs/toy-train", TOY_TRAINER),
                       ("configs/toy-train4", TOY_TRAINER4),
                       ("traffic/toy-chat", TOY_CHAT),
                       ("traffic/toy-doc", TOY_DOC),
                       ("traffic/toy-job", TOY_JOB)):
        with open(os.path.join(root, "benchmark", name + ".json"), "w") as f:
            json.dump(data, f)
    with open(os.path.join(root, "benchmark", "layer_metrics",
                           "toy_bursts.py"), "w") as f:
        f.write(DUMMY_READER)
    with open(os.path.join(root, "benchmark", "families",
                           "toy_family.py"), "w") as f:
        f.write(DUMMY_FAMILY)
    assert before == {k: v for k, v in _digest(root).items() if k in before}
    cells = {"toy-chat": ("toy-serve", "toy-chat"),
             "toy-doc": ("toy-serve", "toy-doc"),
             "toy-train": ("toy-train", "toy-job"),
             "toy-train4": ("toy-train4", "toy-job")}
    for cfg in ("toy-serve", "toy-train", "toy-train4"):
        bench["configs"].append({
            "name": cfg, "source": "none", "reduced": [], "why": "toy",
            "file": f"benchmark/configs/{cfg}.json"})
    for cell, (cfg, traffic) in cells.items():
        bench["workloads"].append({"name": cell, "config": cfg,
                                   "traffic": traffic, "chips": 1,
                                   "why": "toy"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" not in m:
            continue
        if "train-2k-fsdp4" in m["workloads"]:
            m["workloads"].append("toy-train4")
        if "train-2k" in m["workloads"]:
            m["workloads"].append("toy-train")
        if "serve-doc" in m["workloads"]:
            m["workloads"].append("toy-doc")
        if "serve-chat" in m["workloads"]:
            m["workloads"].append("toy-chat")
    bench["per_layer"].append({
        "name": "toy_bursts", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "engine scheduler",
        "moves": "ttft_p90_ms", "workloads": ["toy-chat"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def _digest(root: str) -> dict:
    out = {}
    for d, _, files in os.walk(os.path.join(root, "benchmark")):
        for name in files:
            path = os.path.join(d, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = hash(f.read())
    return out


def run_cell(root: str, workload: str, *extra, seconds: float = 2.0,
             trace: int = 0, seed: int = 3, rehearse: bool = True,
             devices: int = 1):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
               JAX_COMPILATION_CACHE_DIR=os.path.join(root, ".jax_cache"))
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    cmd = [sys.executable, os.path.join("benchmark", "run.py"),
           "--workload", workload, "--seed", str(seed),
           "--seconds", str(seconds), "--trace", str(trace), *extra]
    if rehearse:
        cmd.append("--rehearse")
    return subprocess.run(cmd, cwd=root, env=env, capture_output=True,
                          text=True, timeout=300)
