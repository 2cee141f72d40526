"""Operations and bytes from shapes: the yardstick's arithmetic, kept with
the benchmark so that no later PR can move it. ``cfg`` is a configuration
file's ``model`` group (published key names)."""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """The chip's published peaks. A device that is not in the table is an
    error, not a default."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise SystemExit(f"benchmark: no peaks for device kind "
                         f"{device_kind!r} in benchmark/peaks.json")
    return table[device_kind]


def matmul_params(m: dict) -> int:
    """Parameters that take part in a matrix multiplication per token:
    the blocks and the output head, not the embedding lookup."""
    d, ff = m["hidden_size"], m["intermediate_size"]
    q = m["num_attention_heads"] * m["head_dim"]
    kv = m["num_key_value_heads"] * m["head_dim"]
    block = d * q + 2 * d * kv + q * d + 3 * d * ff
    return m["num_hidden_layers"] * block + d * m["vocab_size"]


def total_params(m: dict) -> int:
    d = m["hidden_size"]
    norms = (2 * m["num_hidden_layers"] + 1) * d
    return matmul_params(m) + d * m["vocab_size"] + norms


def attention_flops_fwd(m: dict, batch: int, seq: int) -> float:
    """Causal attention, forward, over ``batch`` sequences of ``seq``: QK^T
    and PV, 2 operations a multiply-add, half the square being masked."""
    q = m["num_attention_heads"] * m["head_dim"]
    return m["num_hidden_layers"] * batch * 0.5 * (4.0 * seq * seq * q)


def train_flops_per_token(m: dict, seq: int) -> float:
    """Forward plus backward (3 x forward) of a causal LM at sequence
    length ``seq``; recomputation is not counted."""
    return 6.0 * matmul_params(m) + 3.0 * attention_flops_fwd(m, 1, seq) / seq


def flash_train_cost(m: dict, batch: int, seq: int) -> dict:
    """What the flash kernels of one train step (forward, dq, dk/dv) must
    do: operations (backward = 2.5 x forward: it recomputes the scores) and
    HBM bytes (q, k, v, o read or written once forward; q, k, v, o, do
    read and dq, dk, dv written backward), bf16."""
    layers = m["num_hidden_layers"]
    q = m["num_attention_heads"] * m["head_dim"]
    kv = m["num_key_value_heads"] * m["head_dim"]
    fwd = attention_flops_fwd(m, batch, seq)
    tok = batch * seq * layers * 2          # bf16 bytes per unit width
    fwd_bytes = tok * (2 * q + 2 * kv)
    bwd_bytes = tok * (4 * q + 4 * kv)
    return {"flops": 3.5 * fwd, "bytes": fwd_bytes + bwd_bytes}


def decode_step_bytes(m: dict, live_kv_tokens: float) -> float:
    """HBM bytes one decode step must move: every weight once (bf16; the
    embedding rows read are negligible) and the live keys and values
    once."""
    kv = m["num_key_value_heads"] * m["head_dim"]
    weights = 2.0 * matmul_params(m)
    cache = 2.0 * 2 * m["num_hidden_layers"] * kv * live_kv_tokens
    return weights + cache


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak: dict) -> tuple:
    """Least time the chip could take over the time it took, in percent,
    and which roof bounds it."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    bound = "compute" if t_flops >= t_bytes else "memory"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
