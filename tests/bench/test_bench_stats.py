"""The benchmark's metric arithmetic: percentiles, the serving window's
accounting, the chip's rooflines, and the operations and bytes that the
configurations' family counts from shapes."""

import json
import math
import os

import pytest

from benchmark import families, flops, stats, systems
from benchmark.generators.grid import Req

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def req(due, times, new=None, failed=False):
    r = Req(rid=0, prompt=[1], max_new_tokens=new or len(times), due=due)
    r.token_times, r.failed = list(times), failed
    return r


@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4, 5], 50, 3), ([1, 2, 3, 4], 50, 2.5),
    (list(range(1, 101)), 90, 90.1), ([7], 95, 7),
    ([1, 2, math.inf], 50, 2), ([1, 2, math.inf], 90, math.inf)])
def test_percentile(values, q, want):
    assert stats.percentile(values, q) == pytest.approx(want)


def test_percentile_matches_numpy():
    import numpy as np

    xs = list(np.random.default_rng(0).lognormal(size=137))
    for q in (50, 90, 95, 99):
        assert stats.percentile(xs, q) == pytest.approx(
            float(np.percentile(xs, q)))


def test_window_measures_requests_due_inside_it_only():
    ramp, inside, drain = req(7.9, [8.5]), req(8.0, [9.0]), req(59.0, [60])
    got = stats.measured([ramp, inside, drain], 8.0, 59.0)
    assert got == [inside]


def test_ttft_is_from_the_due_time_and_a_failure_misses():
    assert stats.ttft_ms(req(10.0, [10.25, 10.3])) == pytest.approx(250)
    assert stats.ttft_ms(req(10.0, [])) == math.inf
    assert stats.ttft_ms(req(10.0, [10.1], failed=True)) == math.inf
    tail = [stats.ttft_ms(req(0.0, [0.1 * i])) for i in range(1, 10)]
    tail.append(stats.ttft_ms(req(0.0, [], failed=True)))
    assert stats.percentile(tail, 50) < math.inf
    assert stats.percentile(tail, 95) == math.inf


def test_tpot_floor_of_64_tokens():
    short = req(0.0, [1.0 + 0.7 * (i // 16) for i in range(63)])
    assert stats.tpot_ms(short) is None
    times = [1.0 + 0.05 * i for i in range(64)]
    assert stats.tpot_ms(req(0.0, times)) == pytest.approx(50.0)
    # a qualifying request cut short or failed misses, it is not dropped
    assert stats.tpot_ms(req(0.0, times[:10], new=64)) == math.inf
    assert stats.tpot_ms(req(0.0, times, failed=True)) == math.inf


def test_tokens_in_window_counts_arrivals_not_requests():
    a = req(0.0, [9.9, 10.0, 10.5, 61.0])       # a ramp request, streaming
    b = req(20.0, [20.5, 60.999])
    assert stats.tokens_in_window([a, b], 10.0, 61.0) == 4


def test_spread_is_the_contracts():
    import statistics

    xs = [100, 101, 102, 103, 104, 120]
    q = statistics.quantiles(xs, n=4)
    assert stats.spread(xs) == pytest.approx((q[2] - q[0]) / 102.5)


def config(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name,layers,billions", [
    ("mistral-7b-v0.3-d4", 4, 1.14), ("mistral-7b-v0.3-d10", 10, 2.45),
    ("mistral-7b-v0.3-d12", 12, 2.89)])
def test_config_files_keep_published_widths(name, layers, billions):
    c = config(name)
    assert c["num_hidden_layers"] == layers and c["reduced"] == [
        "num_hidden_layers"]
    assert (c["hidden_size"], c["intermediate_size"], c["vocab_size"],
            c["num_attention_heads"], c["num_key_value_heads"],
            c["head_dim"]) == (4096, 14336, 32768, 32, 8, 128)
    assert c["rope_theta"] == 1e6 and c["sliding_window"] is None
    total_params = systems.family(c).total_params
    assert total_params(c) / 1e9 == pytest.approx(billions, abs=0.01)
    full = dict(c, num_hidden_layers=32)
    assert total_params(full) / 1e9 == pytest.approx(7.25, abs=0.01)


def test_train_flops_per_token():
    c = config("mistral-7b-v0.3-d4")
    assert systems.family(c).matmul_params(c) == 4 * 218103808 + 4096 * 32768
    per_token = systems.family(c).train_flops_per_token(c, 2048)
    # 6 x matmul parameters, plus causal attention: 3 x 4 layers x
    # (4 x 2048 x 4096 / 2) operations a token
    assert per_token == pytest.approx(6 * 1006632960 + 12 * 16777216)


def test_flash_cost_and_roofline_bound():
    c = config("mistral-7b-v0.3-d4")
    cost = systems.family(c).flash_train_cost(c, 6, 2048)
    assert cost["flops"] == pytest.approx(
        3.5 * 4 * 6 * 0.5 * 4 * 2048 * 2048 * 4096)
    peak = flops.peaks("TPU v5 lite")
    share, bound = flops.roofline_share(cost["flops"], cost["bytes"],
                                        cost["flops"] / 100e12, peak)
    assert bound == "compute" and share == pytest.approx(100 / 1.97, rel=1e-3)
    share, bound = flops.roofline_share(1.0, 819e9, 2.0, peak)
    assert bound == "memory" and share == pytest.approx(50.0)


def test_decode_step_bytes_and_unknown_device():
    c = config("mistral-7b-v0.3-d12")
    family = systems.family(c)
    weights = 2 * family.matmul_params(c)
    assert family.decode_step_bytes(c, {}) == weights
    per_token = 2 * 2 * 12 * 8 * 128          # k and v, bf16, every layer
    assert family.decode_step_bytes(
        c, {"live_kv_tokens_mean": 1000, "max_batch": 32}) == (
            weights + 1000 * per_token)
    with pytest.raises(SystemExit):
        flops.peaks("TPU v9 imaginary")


def test_a_family_is_found_by_name_and_held_to_the_whole_contract(tmp_path,
                                                                 monkeypatch):
    c = config("mistral-7b-v0.3-d4")
    assert all(callable(getattr(systems.family(c), n)) for n in families.API)
    with pytest.raises(SystemExit, match="no model family 'no_such"):
        systems.family({"family": "no_such"})
    # a family that leaves a count out is refused when it is loaded, not
    # at the first reader that asks on the chip
    (tmp_path / "half.py").write_text(
        "def model_config(c): ...\ndef init_params(m, k): ...\n"
        "def logits(c, p, t): ...\n")
    monkeypatch.setattr(families, "__path__",
                        list(families.__path__) + [str(tmp_path)])
    with pytest.raises(SystemExit, match="train_flops_per_token"):
        systems.family({"family": "half"})
