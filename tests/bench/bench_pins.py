"""Pins by name on ``BENCHMARK.json``, shared by the tests of the cells.

A test of a cell asks that the entries it needs are THERE, each with its
cell under ``workloads``: never that they are the last of a list, the
whole of a list, ``workloads[-1]`` or ``configs[-1]``. So a later PR that
appends a cell, a configuration and a per-layer entry turns none of them
red; ``one_more`` makes such a copy in memory, and the ``bench`` fixture of
``conftest.py`` hands every pin both the file as committed and that copy.

``per_layer`` holds one entry a metric. An entry's name is the reader's
(``benchmark/layer_metrics/<stem>.py``), with a suffix where the cells
that report the quantity report different end-to-end metrics, since an
entry names the one it moves: none for the backlog cells
(``serve_tokens_per_s``), ``.chat`` for the open-loop cells
(``ttft_p90_ms`` / ``tpot_p90_ms``), ``.train`` for the training cells."""

from __future__ import annotations

import copy
import json
import os
import re

from benchmark import harness

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
METRIC_KEYS = {"name", "unit", "better", "source", "layer", "moves"}
# what a configuration may cut (the contract: never a width): depth, the
# lists that say what each layer is, the experts held, the vocabulary
CUTS = re.compile(r"^(num_hidden_layers|n_layer|.*layer_types|.*_per_layer"
                  r"|gating_types|hybrid_override_pattern|num_experts"
                  r"|n_routed_experts|vocab_size)$")
WIDTH = re.compile(r"(_dim|_rank)$|hidden_size|intermediate_size|head_dim"
                   r"|state|expand|proj|num_experts_per_tok")


def committed() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def one_more(bench: dict) -> dict:
    """A copy with what a later PR appends: a configuration (the file of
    one that is there under a new name), a cell of it, the cell's name on
    the end-to-end metric it reports and on one per-layer metric that is
    there, and one per-layer entry more."""
    more = copy.deepcopy(bench)
    more["configs"].append(dict(
        config_entry(bench, "mistral-7b-v0.3-d12"), name="one-more-config"))
    more["workloads"].append({
        "name": "one-more-cell", "config": "one-more-config",
        "traffic": "doc-backlog", "chips": 1, "why": "what a later PR adds"})
    entry(more["end_to_end"], "serve_tokens_per_s")["workloads"].append(
        "one-more-cell")
    entry(more["per_layer"], "decode_roofline")["workloads"].append(
        "one-more-cell")
    more["per_layer"].append({
        "name": "device_idle_share.more", "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "device",
        "moves": "serve_tokens_per_s", "workloads": ["one-more-cell"]})
    return more


def entry(entries: list, name: str) -> dict:
    """The one entry of that name."""
    (found,) = [e for e in entries if e["name"] == name]
    return found


def cell_entry(bench: dict, name: str) -> dict:
    cell = entry(bench["workloads"], name)
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert len(cell["why"]) <= 200 and NAME.match(cell["traffic"])
    return cell


def config_entry(bench: dict, name: str) -> dict:
    found = entry(bench["configs"], name)
    assert set(found) == {"name", "source", "file", "reduced", "why"}
    assert len(found["why"]) <= 200 and len(found["source"]) <= 200
    assert all(NAME.match(k) for k in found["reduced"])
    return found


def config_file(found: dict) -> dict:
    with open(os.path.join(REPO, found["file"])) as f:
        return json.load(f)


def reported(bench: dict, cell: str, group: str = "per_layer") -> dict:
    """{name: entry} of the metrics of ``group`` that the cell reports."""
    return {m["name"]: m for m in harness.cell_metrics(bench, cell, group)}


def stem(name: str) -> str:
    return name.rsplit(".", 1)[0] if name.endswith((".chat", ".train")) \
        else name


def reports(bench: dict, cell: str, stems, moves=None) -> dict:
    """{stem: entry}: each of ``stems`` is there once with ``cell`` in its
    ``workloads``, keeps the contract's keys and has a reader."""
    mine = {}
    for m in reported(bench, cell).values():
        assert stem(m["name"]) not in mine, m["name"]    # one entry a metric
        mine[stem(m["name"])] = m
    missing = sorted(set(stems) - set(mine))
    assert not missing, (cell, missing)
    e2e = reported(bench, cell, "end_to_end")
    for s in stems:
        m = mine[s]
        assert set(m) - {"workloads"} == METRIC_KEYS, m
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["moves"] in e2e, (m["name"], m["moves"])
        assert moves is None or m["moves"] == moves, m["name"]
        assert harness.load_reader(m["name"]) is not None
    return {s: mine[s] for s in stems}


def check_reduced(found: dict, config: dict, published: dict | None = None):
    """The rule ``reduced`` is held to: the entry's list is the file's;
    every key of it differs from the source's value (``reduced_from``) and
    is one a configuration may cut (depth, the layer pattern, the experts
    held, the vocabulary), never a width; and against the ``published``
    values, where the test has them, every key that differs is in it."""
    assert found["reduced"] == config["reduced"] and found["reduced"]
    assert found["source"] == config["source"]
    assert len(found["reduced"]) <= 16
    assert set(config["reduced_from"]) == set(found["reduced"])
    for key in found["reduced"]:
        assert CUTS.match(key) and not WIDTH.search(key), key
        assert config[key] != config["reduced_from"][key], key
    if published is not None:
        differs = sorted(k for k, v in published.items()
                         if config.get(k) != v)
        assert differs == sorted(found["reduced"])
        for key in found["reduced"]:
            was = config["reduced_from"][key]
            # (a long per-layer list is given there in words)
            assert was == published[key] or isinstance(was, str), key
