"""What the per-layer readers share, and the arithmetic of those that
read the client's counters, the train step and the chip as a whole (the
serving programs, the kernels by name and the engine's spans:
``inside.py``). A reader is
``benchmark/layer_metrics/<metric name>.py`` with ``read(run)``; ``run`` is
the harness's RunRecord: ``counters`` (what the runner counted and what it
read from the program's counters), ``trace`` (the reduced device trace of
a traced run on a chip, else None), ``config``, ``traffic``, ``device``.
A reader that finds nothing to read returns None, and the harness leaves
the metric out of the line. Every device number needs the trace: without
one (a CPU rehearsal) the reader returns None. What a number needs of the
model (operations a token, bytes a step, a kernel's cost) is asked of the
configuration's family (``systems.family``), never spelt out here."""

from __future__ import annotations

from benchmark import flops, inside, stats, systems
from benchmark.trace import KERNEL_TARGET


def percentile_ms(run, counter: str, q: float):
    values = run.counters.get(counter)
    return stats.percentile(values, q) * 1e3 if values else None


def median(run, counter: str):
    values = run.counters.get(counter)
    return stats.percentile(values, 50) if values else None


def prefix_hit_share(run):
    """Prompt pages served from the prefix cache, of the full prompt
    pages the window's requests could have reused. (The engine's own
    ``miss_pages`` counts one a lookup, not the pages past the first
    miss, so hit / (hit + miss) overstates.)"""
    hit = run.counters.get("prefix_hit_pages")
    lookups = run.counters.get("prefix_lookup_pages")
    if hit is None or not lookups:
        return None
    return 100.0 * hit / lookups


def decode_roofline(run):
    """Bytes one step must move (the family's count: for a dense model
    the weights once and the live keys and values once) over the chip's
    bandwidth, over the step's time, which is that of the decode programs
    found by name. Memory-bound: at 32 slots a step has 2 x 32 operations
    a weight byte pair, far under the chip's 240 operations a byte."""
    step_ms = inside.decode_program_step_ms(run.trace)
    if not step_ms:
        return None
    nbytes = systems.family(run.config).decode_step_bytes(run.config,
                                                          run.counters)
    if nbytes is None:
        return None
    peak = flops.peaks(run.device["kind"])
    return 100.0 * nbytes / peak["hbm_bytes_per_s"] / (step_ms * 1e-3)


def train_step_ms(run):
    if run.trace is None:
        return None
    seconds, runs = run.trace.module_time(inside.TRAIN_STEP.match, whole=True)
    return seconds / runs * 1e3 if runs else None


def train_mfu(run):
    """Model operations per token (forward and backward, recomputation not
    counted) x tokens/s per chip over the chip's peak. From the run's own
    rate, so only a run on a chip reports it."""
    if run.device["platform"] != "tpu":
        return None
    per_token = systems.family(run.config).train_flops_per_token(
        run.config, run.counters["seq_len"])
    if per_token is None:
        return None
    peak = flops.peaks(run.device["kind"])
    return (100.0 * per_token * run.counters["tokens_per_s_per_chip"]
            / peak["bf16_flops_per_s"])


def _flash_seconds_per_step(run):
    """(kernel seconds, step seconds) of one train step: the kernels'
    share of the step programs' time in the trace, times a whole step."""
    traced, _ = run.trace.module_time(inside.TRAIN_STEP.match)
    whole, runs = run.trace.module_time(inside.TRAIN_STEP.match, whole=True)
    if not runs or not traced:
        return None, None
    kernel = run.trace.op_time(lambda n: KERNEL_TARGET in n)
    return kernel / traced * whole / runs, whole / runs


def flash_roofline(run):
    """The flash kernels (forward, dq, dk/dv) of one step against the
    larger of operations over peak and bytes over bandwidth (the family's
    count of both). Compute-bound at these shapes (head size 128, 2048
    keys)."""
    if run.trace is None:
        return None
    kernel, _ = _flash_seconds_per_step(run)
    if not kernel:
        return None
    c = run.counters
    cost = systems.family(run.config).flash_train_cost(
        run.config, c["batch"] // c["chips"], c["seq_len"])
    if cost is None:
        return None
    share, _ = flops.roofline_share(cost["flops"], cost["bytes"], kernel,
                                    flops.peaks(run.device["kind"]))
    return share


def collective_share(run):
    if run.trace is None or not run.trace.devices:
        return None
    total, _ = run.trace.collective_times()
    return 100.0 * total / run.trace.extent_s


def collective_exposed_share(run):
    if run.trace is None or not run.trace.devices:
        return None
    _, exposed = run.trace.collective_times()
    return 100.0 * exposed / run.trace.extent_s


def device_idle_share(run):
    if run.trace is None or not run.trace.extent_s:
        return None
    return 100.0 * (1.0 - run.trace.busy_s() / run.trace.extent_s)


def peak_hbm_gb(run):
    """Live arrays plus the compiler's count of the window's largest
    program's temporaries, on the fullest chip: not ``peak_bytes_in_use``,
    which on this backend leaves the temporaries out (PERF.md, PR 21)."""
    if run.device["platform"] != "tpu":
        return None
    return run.counters["peak_hbm_bytes"] / 1e9


def compiles_in_window(run):
    return float(run.counters["compiles_in_window"])
