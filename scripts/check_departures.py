"""By hand, on the chip: a configuration's reference check with the plain
reference as it is and with each named departure of its family's
``logits`` in turn (``benchmark/families/<family>.py``), one engine, the
same served tokens. The check must pass as it is and fail under each
departure alone: that is what shows that the comparison sees the
mechanism.

    python3 scripts/check_departures.py --config <name> --seed <n> \
        --departure '{"window": null}' --departure '{"gate": "none"}' ...

Prints one line a reading: the token gap (``benchmark/reference.py``), the
tokens that are not the reference's own, and whether it is within the
harness's limit."""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--departure", action="append", default=[])
    args = ap.parse_args()

    import numpy as np

    from benchmark import reference, serving, systems

    with open(os.path.join("benchmark", "configs",
                           args.config + ".json")) as f:
        config = json.load(f)
    check = config["system"]["reference_check"]
    n, shared, new = (check["prompt_tokens"], check["shared_tokens"],
                      check["new_tokens"])
    rng = np.random.default_rng([args.seed, 9])
    vocab = config["vocab_size"]
    first = rng.integers(1, vocab, n, dtype=np.int32)
    second = np.concatenate([first[:shared], rng.integers(
        1, vocab, n - shared, dtype=np.int32)])
    params = systems.make_params(config, args.seed)
    eng = serving.start_engine(config, params)
    served = [(p, serving.collect(eng, eng.submit(p, max_new_tokens=new),
                                  timeout_s=900.0))
              for p in (first, second)]
    print("prefix pages hit:",
          systems.engine_counters(eng)["prefix_hit_pages"], flush=True)
    error = serving.stop_engine(eng)
    if error is not None:
        raise error
    logits = systems.family(config).logits
    for departure in [{}] + [json.loads(d) for d in args.departure]:
        readings = [reference.token_gap(
            lambda *a: logits(*a, **departure), config, params, p, t)
            for p, t in served]
        gap = max(g for g, _ in readings)
        print(f"departure={json.dumps(departure)} token_gap={gap:.4f} "
              f"not_the_references={sum(m for _, m in readings)} "
              f"within={gap <= serving.TOKEN_GAP_TOL}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
