"""By hand, on the chip: one run of a benchmark cell with per-layer
readers that have no entry in ``BENCHMARK.json`` yet (a reader is
``benchmark/layer_metrics/<name>.py``; an entry is a ``benchmark`` PR's
to add where a pin of ``tests/bench/`` holds the list closed), read
beside the cell's own. The entries are made in memory, for this run
alone; everything else is ``benchmark/run.py``'s.

    python3 scripts/run_cell_readers.py conv_mixer_share,state_restore_share \
        --workload serve-extract-gen --seed 1 --seconds 51 --trace 1
"""

from __future__ import annotations

import os
import sys
import time

T_START = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness  # noqa: E402


def main() -> int:
    names, argv = sys.argv[1].split(","), sys.argv[2:]
    load = harness.load_cell

    def load_cell(workload, root=harness.ROOT):
        bench, cell, config, traffic = load(workload, root)
        known = {m["name"] for m in bench["per_layer"]}
        bench["per_layer"] += [
            {"name": name, "unit": "%", "better": "lower",
             "source": "device_trace", "layer": "unlisted",
             "moves": "serve_tokens_per_s", "workloads": [cell["name"]]}
            for name in names if name not in known]
        return bench, cell, config, traffic

    harness.load_cell = load_cell
    return harness.main(argv, t_start=T_START)


if __name__ == "__main__":
    sys.exit(main())
