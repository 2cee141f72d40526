"""A toy copy of the benchmark for the CPU tests: the repo's ``benchmark``
directory and ``BENCHMARK.json`` copied into a temporary directory, with
configurations, three model families (one that the program runs as a
Llama block, with a reference of its own; the same with a reference that
is wrong on purpose; one that is not Llama-shaped at all: ``models/gpt``
through the trainer), three traffic mixes, six cells and a per-layer
metric ADDED as new files and new entries (no file that is there is
edited)."""

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
    "doc_tokens": {"dist": "uniform", "min": 100, "max": 128},
    "question_tokens": {"dist": "uniform", "min": 4, "max": 12},
    "answer_tokens": {"dist": "uniform", "min": 4, "max": 12},
    "askings": 3, "docs_per_cycle": 4, "wave_docs": 2, "max_waiting": 2,
    "ramp_s": 1, "trace_s": 1, "prefill_limits": LIMITS}
TOY_JOB = {"generator": "train_job", "runner": "train", "seq_len": 64,
           "trace_s": 1}
TOY_SERVE_NOROPE = dict(TOY_ENGINE, name="toy-serve-norope",
                        family="toy_norope")
TOY_GPT = {
    "n_embd": 64, "n_inner": 128, "n_layer": 2, "n_head": 4,
    "n_positions": 128, "vocab_size": 512, "layer_norm_epsilon": 1e-5,
    "activation_function": "gelu_new", "torch_dtype": "bfloat16",
    "family": "toy_gpt", "source": "none: a toy for the CPU tests",
    "reduced": [], "name": "toy-gpt-train", "system": {
        "mesh_axes": {"dp": 1}, "strategy": "dp", "remat": "none",
        "fused_loss": False, "warmup_steps": 1, "batch_sequences": 2}}
TOY_FAMILY = '''"""A model family added by a later PR: one file of its own. The program
runs it as a Llama block, so it keeps that family's adapter and counts;
the plain reference is a copy of its own (``rope=False`` drops the rotary
embedding: the reference that is wrong on purpose, for ``toy_norope``)."""

import jax
import jax.numpy as jnp

from benchmark.families.llama import (  # noqa: F401
    decode_step_bytes, flash_train_cost, init_params, model_config,
    train_flops_per_token)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    s, hd = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    sin, cos = jnp.sin(ang)[None, :, None], jnp.cos(ang)[None, :, None]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def logits(config, params, tokens, rope=True):
    heads, kv, hd = (config[k] for k in (
        "num_attention_heads", "num_key_value_heads", "head_dim"))
    eps, theta = config["rms_norm_eps"], config["rope_theta"]
    b, s = tokens.shape
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embedding"])[tokens]
        for i in range(config["num_hidden_layers"]):
            p = _f32(jax.tree.map(lambda a: a[i], params["blocks"]))
            h = _norm(x, p["attn_norm"], eps)
            q = (h @ p["wq"]).reshape(b, s, heads, hd)
            k = (h @ p["wk"]).reshape(b, s, kv, hd)
            v = (h @ p["wv"]).reshape(b, s, kv, hd)
            if rope:
                q, k = _rope(q, theta), _rope(k, theta)
            k, v = (jnp.repeat(t, heads // kv, axis=2) for t in (k, v))
            sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
            sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
            a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v)
            x = x + a.reshape(b, s, heads * hd) @ p["wo"]
            h = _norm(x, p["mlp_norm"], eps)
            x = x + (jax.nn.silu(h @ p["w_gate"])
                     * (h @ p["w_up"])) @ p["w_down"]
        x = _norm(x, _f32(params["final_norm"]), eps)
        return x @ _f32(params["lm_head"])
'''
NOROPE_FAMILY = '''"""The toy family with a reference that is wrong on purpose: a cell of
this family must read ``correct`` false, which shows that the family's
reference, and no module the harness imports by name, decides."""

from benchmark.families import toy_family
from benchmark.families.toy_family import (  # noqa: F401
    decode_step_bytes, flash_train_cost, init_params, model_config,
    train_flops_per_token)


def logits(config, params, tokens):
    return toy_family.logits(config, params, tokens, rope=False)
'''
GPT_FAMILY = '''"""A family that is not Llama-shaped, added as one file: GPT-2's block
(LayerNorm with biases, learned positions, one fused QKV projection, GELU
feed-forward, tied head) as ``ray_tpu.models.gpt`` trains it, under the
published GPT-2 key names. The engine serves no such block, so there is
nothing to count for a decode step, and its train step has no kernel of
its own."""

import jax
import jax.numpy as jnp


def model_config(config):
    from ray_tpu.models import gpt

    return gpt.GPTConfig(
        vocab_size=config["vocab_size"], max_seq_len=config["n_positions"],
        d_model=config["n_embd"], n_layers=config["n_layer"],
        n_heads=config["n_head"], d_ff=config["n_inner"],
        ln_eps=config["layer_norm_epsilon"], dtype=config["torch_dtype"],
        remat=config["system"].get("remat", "none"))


def init_params(model_cfg, key):
    from ray_tpu.models import gpt

    return gpt.init_params(model_cfg, key)


def _ln(x, w, b, eps):
    mean = x.mean(-1, keepdims=True)
    var = jnp.square(x - mean).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + b


def logits(config, params, tokens):
    heads, eps = config["n_head"], config["layer_norm_epsilon"]
    b, s = tokens.shape
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        x = params["embedding"][tokens] + params["pos_embedding"][:s]
        for i in range(config["n_layer"]):
            p = jax.tree.map(lambda a: a[i], params["blocks"])
            h = _ln(x, p["ln1_w"], p["ln1_b"], eps)
            q, k, v = (t.reshape(b, s, heads, -1) for t in jnp.split(
                h @ p["wqkv"] + p["bqkv"], 3, axis=-1))
            sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
            sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
            a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v)
            x = x + a.reshape(b, s, -1) @ p["wo"] + p["bo"]
            h = _ln(x, p["ln2_w"], p["ln2_b"], eps)
            x = x + (jax.nn.gelu(h @ p["w_up"] + p["b_up"], approximate=True)
                     @ p["w_down"] + p["b_down"])
        x = _ln(x, params["final_ln_w"], params["final_ln_b"], eps)
        return x @ params["embedding"].T


def train_flops_per_token(config, seq_len):
    d, ff = config["n_embd"], config["n_inner"]
    matmul = (config["n_layer"] * (4 * d * d + 2 * d * ff)
              + d * config["vocab_size"])
    attention = config["n_layer"] * 0.5 * 4.0 * seq_len * d
    return 6.0 * matmul + 3.0 * attention


def decode_step_bytes(config, counters):
    return None


def flash_train_cost(config, batch, seq_len):
    return None
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
                       ("configs/toy-serve-norope", TOY_SERVE_NOROPE),
                       ("configs/toy-train", TOY_TRAINER),
                       ("configs/toy-train4", TOY_TRAINER4),
                       ("configs/toy-gpt-train", TOY_GPT),
                       ("traffic/toy-chat", TOY_CHAT),
                       ("traffic/toy-doc", TOY_DOC),
                       ("traffic/toy-job", TOY_JOB)):
        with open(os.path.join(root, "benchmark", name + ".json"), "w") as f:
            json.dump(data, f)
    with open(os.path.join(root, "benchmark", "layer_metrics",
                           "toy_bursts.py"), "w") as f:
        f.write(DUMMY_READER)
    for family, text in (("toy_family", TOY_FAMILY),
                         ("toy_norope", NOROPE_FAMILY),
                         ("toy_gpt", GPT_FAMILY)):
        with open(os.path.join(root, "benchmark", "families",
                               family + ".py"), "w") as f:
            f.write(text)
    assert before == {k: v for k, v in _digest(root).items() if k in before}
    cells = {"toy-chat": ("toy-serve", "toy-chat"),
             "toy-doc": ("toy-serve", "toy-doc"),
             "toy-doc-norope": ("toy-serve-norope", "toy-doc"),
             "toy-train": ("toy-train", "toy-job"),
             "toy-train4": ("toy-train4", "toy-job"),
             "toy-gpt-train": ("toy-gpt-train", "toy-job")}
    for cfg in sorted({cfg for cfg, _ in cells.values()}):
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
            m["workloads"] += ["toy-train", "toy-gpt-train"]
        if "serve-doc" in m["workloads"]:
            m["workloads"] += ["toy-doc", "toy-doc-norope"]
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
