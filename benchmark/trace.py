"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read. Part of the yardstick: no later PR changes how a number is
taken from a trace.

What a TPU trace holds (looked at by hand, PERF.md PR 23): one plane
``/device:TPU:<n>`` per chip with the lines ``XLA Modules`` (one event per
run of a jitted program, named ``jit_<function>(<hash>)``), ``XLA Ops``
(one event per HLO operation run, named by its whole HLO text) and
``Async XLA Ops`` (copies and collectives in flight, start to done); and
``/host:CPU`` with one line per thread, where ``jax.profiler
.TraceAnnotation`` spans appear under their own names: the benchmark's
own (``bench.*``, ``harness.span``) and, since PR 24, the phases of the
program's engine loop (``engine.*``). Device and host events are on one
clock to within a millisecond or two.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

COLLECTIVE_OPCODES = ("all-gather", "all-reduce", "all-to-all",
                      "collective-permute", "reduce-scatter",
                      "collective-broadcast")
CONTAINERS = ("while", "conditional", "call")
KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'   # a Pallas kernel
_OPCODE = re.compile(r"(?:^|\s)([a-z][a-z0-9\-]*)\(")
_SHAPE = re.compile(r"([a-z]+[0-9]*)\[([0-9,]*)\]")
# The host spans kept, by whose they are, in the order in which they name
# an idle gap: the program's own phases first (its loop is the thread that
# feeds the device), then the benchmark's (the client's thread).
SPAN_PREFIXES = ("engine.", "bench.")


def find_xplane(trace_dir: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def opcode(op_name: str) -> str:
    """The HLO opcode of an ``XLA Ops`` event name (its HLO text)."""
    rhs = op_name.split(" = ", 1)[-1]
    m = _OPCODE.search(rhs)
    return m.group(1) if m else ""


def is_kernel_call(op_name: str, kernel: str) -> bool:
    """Whether an ``XLA Ops`` event is a call of the Pallas kernel whose
    ``pallas_call`` is named ``kernel`` (the instruction is
    ``%<kernel>[.N] = ... custom_call_target="tpu_custom_call"``)."""
    return KERNEL_TARGET in op_name and op_name.lstrip("%").startswith(kernel)


def result_dims(op_name: str) -> list:
    """The dimensions of an operation's result, from its HLO text; [] for
    a scalar or where the text shows none."""
    shape = _SHAPE.search(op_name.partition(" = ")[2])
    return [int(x) for x in shape.group(2).split(",") if x] if shape else []


def is_collective(op_name: str) -> bool:
    oc = opcode(op_name)
    if oc.startswith(COLLECTIVE_OPCODES):
        return True
    # a collective fused with compute keeps its opcode in the fusion's name
    head = op_name.split(" = ", 1)[0]
    return oc == "fusion" and any(c in head for c in COLLECTIVE_OPCODES)


def label(op_name: str) -> str:
    """A short name for the breakdown: the operation's own name and its
    result shape, in the characters a metric name may have."""
    head, _, rhs = op_name.partition(" = ")
    shape = _SHAPE.search(rhs)
    text = head.lstrip("%")
    if shape:
        text += "__" + shape.group(1) + "_" + shape.group(2).replace(",", "_")
    return re.sub(r"[^A-Za-z0-9_.\-]", "_", text)[:64]


def union_length(intervals) -> float:
    """Total length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def merged(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def subtract_length(a, b) -> float:
    """Length of union(a) that union(b) does not cover."""
    a, b = merged(a), merged(b)
    total, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


def _busy_intervals(dev: dict) -> list:
    """When an operation ran on a chip: its ``XLA Ops`` events but loops
    and branches (their events span their bodies, gaps included), and the
    collectives in flight. A prefetch copy in flight between two programs
    is not the device working."""
    return ([(s, e) for n, s, e in dev["ops"] if opcode(n) not in CONTAINERS]
            + [(s, e) for n, s, e in dev["async_ops"] if is_collective(n)])


class Trace:
    """Events of one trace, in seconds. ``devices`` is a list (one entry a
    chip) of dicts ``modules``, ``ops``, ``async_ops``, each a list of
    (name, start, end); ``spans`` is the host spans kept (``SPAN_PREFIXES``),
    of every thread, in the order they started."""

    def __init__(self, devices: list, spans: list, extent_s=None):
        self.devices = devices
        self.spans = sorted(spans, key=lambda x: x[1])
        # the traced window: from the first to the last event of any
        # plane, host threads included. (The time between start_trace and
        # stop_trace on the host's clock is shorter: stopping takes a
        # while, and the device is traced until it has stopped.)
        self.extent_s = extent_s

    @classmethod
    def from_file(cls, path: str) -> "Trace":
        import jax

        data = jax.profiler.ProfileData.from_file(path)
        devices, spans = [], []
        first, last = float("inf"), 0.0
        keys = {"XLA Modules": "modules", "XLA Ops": "ops",
                "Async XLA Ops": "async_ops"}
        for plane in data.planes:
            device = plane.name.startswith("/device:TPU:")
            host = plane.name.startswith("/host:")
            dev = {"modules": [], "ops": [], "async_ops": []}
            for line in plane.lines:
                key = keys.get(line.name) if device else None
                for e in line.events:
                    start, end = e.start_ns, e.start_ns + e.duration_ns
                    first, last = min(first, start), max(last, end)
                    if key:
                        dev[key].append((e.name, start * 1e-9, end * 1e-9))
                    elif host and e.name.startswith(SPAN_PREFIXES):
                        spans.append((e.name, start * 1e-9, end * 1e-9))
            if device:
                devices.append(dev)
        return cls(devices, spans, max(0.0, last - first) * 1e-9)

    # -- device time ------------------------------------------------------

    def busy_s(self) -> float:
        """Seconds in which an operation ran on the device: the union of
        its operations' intervals, averaged over the chips."""
        if not self.devices:
            return 0.0
        return sum(union_length(_busy_intervals(d))
                   for d in self.devices) / len(self.devices)

    def _modules(self, whole: bool) -> list:
        """Program runs on the first chip (every chip runs the same
        programs). ``whole`` leaves out the first and the last run of the
        trace: the trace's edges cut them, so their durations are short."""
        if not self.devices:
            return []
        mods = sorted(self.devices[0]["modules"], key=lambda x: x[1])
        return mods[1:-1] if whole else mods

    def module_time(self, match, whole: bool = False) -> tuple:
        """(seconds, runs) of the programs whose name ``match`` accepts.
        For a time per run, ask for ``whole`` runs; for a share of the
        window, for all."""
        ev = [(s, e) for n, s, e in self._modules(whole) if match(n)]
        return sum(e - s for s, e in ev), len(ev)

    def op_time(self, match) -> float:
        """Seconds of the operations ``match`` accepts, a chip's mean."""
        if not self.devices:
            return 0.0
        return sum(e - s for d in self.devices for n, s, e in d["ops"]
                   if match(n)) / len(self.devices)

    def collective_times(self) -> tuple:
        """(seconds with a collective in flight, seconds of those with no
        other operation running), a chip's mean."""
        if not self.devices:
            return 0.0, 0.0
        total = exposed = 0.0
        for d in self.devices:
            coll = [(s, e) for n, s, e in d["ops"] + d["async_ops"]
                    if is_collective(n)]
            compute = [(s, e) for n, s, e in d["ops"]
                       if not is_collective(n)]
            total += union_length(coll)
            exposed += subtract_length(coll, compute)
        return total / len(self.devices), exposed / len(self.devices)

    # -- the breakdown ----------------------------------------------------

    def programs(self, k: int = 8) -> list:
        """[(program name, runs, seconds)] on the first chip, longest
        first: which jitted programs the window ran."""
        if not self.devices:
            return []
        acc = defaultdict(lambda: [0, 0.0])
        for n, s, e in self.devices[0]["modules"]:
            acc[n][0] += 1
            acc[n][1] += e - s
        top = sorted(acc.items(), key=lambda kv: -kv[1][1])[:k]
        return [(n, runs, t) for n, (runs, t) in top]

    def top_ops(self, k: int = 10) -> list:
        """The operations that took most device time on the first chip.
        A loop or a branch is left out: its event spans its body's
        operations, which are counted themselves."""
        if not self.devices:
            return []
        acc = defaultdict(float)
        for n, s, e in self.devices[0]["ops"]:
            if opcode(n) in CONTAINERS:
                continue
            acc[n] += e - s
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
        return [[label(n), t] for n, t in top]

    def idle_gaps(self, k: int = 10) -> list:
        """The longest gaps between operations on the first chip, each
        named by what the host was doing in most of it, or
        ``no-benchmark-span``. A phase of the program goes before a span
        of the benchmark (the order of ``SPAN_PREFIXES``:
        ``engine.wait_device``, not the client's ``bench.sleep``). Among
        the spans of one kind the innermost counts: a span is given only
        the part of the gap that no shorter span covers, so an iteration
        names what none of its phases does."""
        if not self.devices:
            return []
        d = self.devices[0]
        busy = merged(_busy_intervals(d))
        gaps = sorted(((b[0] - a[1], a[1], b[0])
                       for a, b in zip(busy, busy[1:])), reverse=True)[:k]
        out = []
        for length, s, e in gaps:
            over = []
            for name, ss, se in self.spans:
                if ss >= e:
                    break
                if se > s:
                    over.append((se - ss, name, (max(s, ss), min(e, se))))
            best = "no-benchmark-span"
            for prefix in SPAN_PREFIXES:
                mine = sorted(x for x in over if x[1].startswith(prefix))
                if mine:
                    own = [subtract_length([clip], [c for _, _, c in mine[:i]])
                           for i, (_, _, clip) in enumerate(mine)]
                    best = mine[own.index(max(own))][1]
                    break
            out.append([best, length])
        return out
