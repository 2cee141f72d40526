"""Per-layer metric ``prefill_run_p50_ms.*`` (see benchmark/inside.py).
It comes from the watcher thread's stamps on the host's clock; where the
run has a device trace, the same runs on the device's clock are said
beside it, which is the check on those stamps."""

from benchmark import inside, program_spans
from benchmark.harness import say


def read(run):
    spans = program_spans.engine_spans()
    if spans and run.trace is not None:
        say("prefill_run", **inside.stamp_check(spans, run.trace))
    return inside.prefill_run_p50_ms(spans)
