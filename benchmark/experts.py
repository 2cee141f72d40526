"""Arithmetic of the per-layer metrics of a routed-expert feed-forward
(PR 28): its share of the decode programs' device time, found in the
reduced trace, and what the decode program counted of its own routing,
read from the engine loop's spans (``program_spans``). Pure functions of a
``Trace`` or a list of span records, so the CPU tests run them on
synthetic ones; each returns None where there is nothing to read, or fewer
than ``inside.MIN_SAMPLES`` samples.

An XLA fusion keeps no scope name in a trace (an operation's event is
named by its HLO text: instruction name, shapes, operands), so which
operations are the feed-forward's is the family's to say, from the shapes
its experts have: ``expert_ffn_op(config)`` of
``benchmark/families/<family>.py``. A family without it has no such layer
and its cells list none of these metrics."""

from __future__ import annotations

from benchmark import inside
from benchmark.trace import CONTAINERS, opcode


def ops_inside(trace, programs, accept) -> tuple:
    """([(operation's name, seconds)], runs, the runs' seconds): the
    operations ``accept`` takes that began inside a run of a program whose
    name ``programs`` (a compiled pattern) matches, on the first chip,
    each cut at its run's end. A loop or a branch is left out: its event
    spans its body's operations."""
    dev = trace.devices[0]
    runs = sorted((s, e) for n, s, e in dev["modules"] if programs.match(n))
    found, i = [], 0
    for name, s, e in sorted(dev["ops"], key=lambda x: x[1]):
        while i < len(runs) and runs[i][1] <= s:
            i += 1
        if i == len(runs):
            break
        if (s >= runs[i][0] and opcode(name) not in CONTAINERS
                and accept(name)):
            found.append((name, min(e, runs[i][1]) - s))
    return found, len(runs), sum(e - s for s, e in runs)


def expert_ffn_share(trace, is_expert_op):
    """Device time of the operations ``is_expert_op`` accepts that ran
    inside a run of a decode program, over those runs' time."""
    if trace is None or not trace.devices:
        return None
    found, runs, total = ops_inside(trace, inside.DECODE, is_expert_op)
    if runs < inside.MIN_SAMPLES or total <= 0:
        return None
    return 100.0 * sum(s for _, s in found) / total


def chunk_stat_mean(spans, stat: str):
    """Mean over the decode chunks emitted in the slice of a statistic the
    decode program took of its feed-forward (itself the mean over the
    chunk's layer-steps), from the chunk's ``engine.emit`` span."""
    values = [s["attrs"][stat] for s in spans or ()
              if s["name"] == "engine.emit"
              and s.get("attrs", {}).get("what") == "chunk"
              and stat in s["attrs"]]
    if len(values) < inside.MIN_SAMPLES:
        return None
    return sum(values) / len(values)
