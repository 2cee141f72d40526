"""By hand, on the chip: a configuration's reference check, seed after
seed, and on the first of them with each named departure of its family's
``logits`` in turn (``benchmark/families/<family>.py``), on the same
served tokens. The check is the harness's own arithmetic
(``benchmark/reference.py:token_gap`` of the two prompts of
``system.reference_check``, the second reusing the first's pages, against
``benchmark/serving.py:TOKEN_GAP_TOL``); the engine is stopped before the
reference runs, so this says nothing of whether the reference fits beside
the engine (the cell's own run does). What a configuration's ``init``
scales are chosen by: every seed must read within the limit as it is, and
outside it under each departure alone.

    python3 scripts/check_seeds.py --config <name> --seeds 1,2,3 \
        --departure '{"indexer": "none"}' --departure-seeds 2 \
        --init _QUERY_GAIN=2.5

``--init NAME=FLOAT`` sets a constant of the model's module before any
weight is made (a scale under trial). One JSON line a seed."""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import weakref

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", required=True,
                    help="whole numbers, comma-separated")
    ap.add_argument("--departure", action="append", default=[])
    ap.add_argument("--departure-seeds", type=int, default=0,
                    help="how many of the seeds, from the first, are also "
                    "read under each departure")
    ap.add_argument("--init", action="append", default=[],
                    metavar="NAME=FLOAT")
    args = ap.parse_args()

    import numpy as np

    from benchmark import reference, serving, systems
    from ray_tpu._private.accelerator import enable_compile_cache

    enable_compile_cache()
    with open(os.path.join("benchmark", "configs",
                           args.config + ".json")) as f:
        config = json.load(f)
    module = sys.modules[type(systems.model_config(config)).__module__]
    for item in args.init:
        name, _, value = item.partition("=")
        assert hasattr(module, name), name
        setattr(module, name, float(value))
    check = config["system"]["reference_check"]
    n, shared, new = (check["prompt_tokens"], check["shared_tokens"],
                      check["new_tokens"])
    vocab = config["vocab_size"]
    logits = systems.family(config).logits
    departures = [json.loads(d) for d in args.departure]
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        rng = np.random.default_rng([seed, 9])
        first = rng.integers(1, vocab, n, dtype=np.int32)
        second = np.concatenate([first[:shared], rng.integers(
            1, vocab, n - shared, dtype=np.int32)])
        params = systems.make_params(config, seed)
        eng = serving.start_engine(config, params)
        served = [(p, serving.collect(
            eng, eng.submit(p, max_new_tokens=new), timeout_s=900.0))
            for p in (first, second)]
        hits = systems.engine_counters(eng)["prefix_hit_pages"]
        error = serving.stop_engine(eng)
        if error is not None:
            raise error
        # its page pool has to go before the reference's logits are made
        gone, eng = weakref.ref(eng), None
        serving.wait_gone(gone)
        gc.collect()
        line = {"seed": seed, "init": args.init, "prefix_hit_pages": hits}
        for departure in [{}] + departures * (i < args.departure_seeds):
            readings = [reference.token_gap(
                lambda *a: logits(*a, **departure), config, params, p, t)
                for p, t in served]
            gap = max(g for g, _ in readings)
            key = json.dumps(departure) if departure else "token_gap"
            line[key] = round(gap, 4)
            if not departure:
                line["not_the_references"] = sum(m for _, m in readings)
                line["correct"] = gap <= serving.TOKEN_GAP_TOL
        print(json.dumps(line), flush=True)
        del params, served
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
