"""What each operation of a traced slice is a piece OF, by the names the
device programs give their pieces (PR 57): the join *scope -> compiled
instruction -> trace event*, and the shares read from it.

An ``XLA Ops`` event is named by its HLO text and carries no scope, but
the program records, when a traced engine stops, what every instruction
of the programs it dispatched is a piece of
(``ray_tpu/util/program_scopes.py``: ``{instruction: [result shape,
scope]}`` an executable, from the ``jax.named_scope``s of
``ray_tpu/ops/scopes.py`` as the compiled text keeps them). This module
reads those records (``scope_maps``: with ``program_spans.py`` and
``systems.py`` the places the benchmark touches the program) and joins a
reduced trace to them. A program from before PR 57 has no such accessor:
then there is no map, every reader here returns None and the ``bench
scopes:`` line says so.

The join: an operation lies inside the run (an ``XLA Modules`` event) that
contains its start, cut at the run's end; loops, branches and calls are
left out as ``Trace.top_ops`` leaves them out (their events span their
bodies'). Its instruction is the head of its name, its shape the first
``dtype[dims]`` behind the ``=``. A run's executable is the recorded map
of the run's program name (``jit_x`` of ``jit_x(<number>)``) in which the
run's operations are instructions of the same shape (``FIT`` of them: all
but a stray one in a hundred, so that one event whose text the program's
parser reads otherwise costs that event and not the run): a program name
has several executables (a prefill program specialises by ``group x
bucket``) and their instruction names collide. Where none or several that
disagree are left, the run's operations count as UNJOINED. The choice is
made once for each ``jit_x(<number>)``, on its run of most operations (the
slice's edges cut runs), and kept for its other runs (the number tells
executables apart in the trace; it is no part of the backend's fingerprint
of the executable: looked for on the chip, PR 57, and not found); an
operation that the kept map does not know by its shape counts as unjoined
by itself.

A map is the EXECUTABLE's: one a persistent compile cache handed back
(its key strips name stacks) carries the scopes of whichever tree compiled
that text first, every event still joins, and the shares would be that
tree's. So the prefill shares are WITHHELD (None, and a word in the line)
where the prefill runs' own unscoped plus unjoined time passes ``HOLE``
percent, and ``unscoped_share`` is the LARGER of the prefill's and the
decode's, not the two pooled: decode dominates a slice and is compiled
anew by every tree (its kernels' calls carry source locations into the
cache's key), so a pooled gauge would not see stale prefill maps.

Pure functions of a ``Trace`` and a list of maps, so the CPU tests run
them on synthetic ones. Shares are percent of the matched programs'
device time in the slice (their runs' seconds), so a share of "" is time
in operations no scope names and the gaps inside a run are in no share.
None under ``inside.MIN_SAMPLES`` runs or with no map. Nothing here
raises into a reader: a record of another shape gives None and a word in
the line."""

from __future__ import annotations

import functools
import re
from collections import Counter

from benchmark import inside
from benchmark.harness import say
from benchmark.trace import CONTAINERS, opcode

UNSCOPED, UNJOINED = "", "(unjoined)"
FIT = 0.99      # of a run's operations, to take a map for its executable
HOLE = 10.0     # percent of a kind's time unnamed: past it, no share of it
ROUTED = ("moe_router", "moe_dispatch", "moe_experts", "moe_combine",
          "shared_expert")
COMBINE = ("moe_dispatch", "moe_combine")
ATTENTION = ("attn", "latent_attn", "index_select")
DENSE = ("attn_qkv", "attn_out", "ffn", "lm_head")
_SHAPE = re.compile(r"[a-z]+[0-9]*\[[0-9,]*\]")


def instruction(op_name: str) -> tuple:
    """(instruction name, result shape) of an ``XLA Ops`` event's name:
    ``%fusion.563 = f32[64,32,768]{2,1,0:T(8,128)} fusion(..`` ->
    (``fusion.563``, ``f32[64,32,768]``)."""
    head, _, rest = op_name.partition(" = ")
    shape = _SHAPE.search(rest)
    return head.strip().lstrip("%"), shape.group(0) if shape else ""


def _fits(scopes: dict, found: list) -> bool:
    known = sum(scopes.get(name, (None,))[0] == shape
                for name, shape, _ in found)
    return known >= FIT * len(found)


def executable_of(candidates: list, found: list):
    """The one of ``candidates`` (``scopes`` maps of one program name) in
    which the operations of ``found`` ([(instruction, shape, seconds)])
    are instructions of the same shape (``FIT``), or None. Several may
    fit (the same program compiled twice): they count as one where they
    give the run's operations the same scopes."""
    fits = [scopes for scopes in candidates if _fits(scopes, found)]
    said = {tuple(scopes.get(name, (None, None))[1] for name, _, _ in found)
            for scopes in fits}
    return fits[0] if len(said) == 1 else None


def scope_seconds(trace, maps, programs) -> dict:
    """What the runs of the programs whose name ``programs`` (a compiled
    pattern) matches were made of, on the first chip: ``seconds`` (a
    Counter {scope: seconds}, with ``UNSCOPED`` and ``UNJOINED``),
    ``inferred`` (a Counter {rule: seconds}: the part of ``seconds`` under
    a scope that is not the instruction's own but its operand's or its
    user's, as the record says), ``runs``, ``total`` (the runs' seconds),
    ``ops`` and ``joined`` (the operations counted, and those that found
    their instruction)."""
    dev = trace.devices[0]
    by_program: dict = {}
    rules: dict = {}            # id(scopes) -> {instruction: rule}
    for record in maps:
        by_program.setdefault(record["program"], []).append(record["scopes"])
        rules[id(record["scopes"])] = record.get("inferred") or {}
    runs = sorted((s, e, n) for n, s, e in dev["modules"]
                  if programs.match(n))
    inside_run = [[] for _ in runs]
    i = 0
    for name, s, e in sorted(dev["ops"], key=lambda x: x[1]):
        while i < len(runs) and runs[i][1] <= s:
            i += 1
        if i == len(runs):
            break
        if s >= runs[i][0] and opcode(name) not in CONTAINERS:
            inside_run[i].append((*instruction(name),
                                  min(e, runs[i][1]) - s))
    fullest: dict = {}
    for (_, _, run), found in zip(runs, inside_run):
        if len(found) > len(fullest.get(run, ())):
            fullest[run] = found
    chosen = {run: executable_of(by_program.get(run.split("(", 1)[0], ()),
                                 found) for run, found in fullest.items()}
    seconds, inferred = Counter(), Counter()
    ops = joined = 0
    for (_, _, run), found in zip(runs, inside_run):
        scopes = chosen.get(run) or {}
        rule = rules.get(id(scopes), {})
        for name, shape, took in found:
            known = scopes.get(name, (None,))
            if known[0] == shape:
                seconds[known[1]] += took
                joined += 1
                if name in rule:
                    inferred[rule[name]] += took
            else:
                seconds[UNJOINED] += took
        ops += len(found)
    return {"seconds": seconds, "inferred": inferred, "runs": len(runs),
            "total": sum(e - s for s, e, _ in runs), "ops": ops,
            "joined": joined}


def shares(found: dict):
    """{scope: percent of the runs' seconds}, or None under
    ``inside.MIN_SAMPLES`` runs."""
    if found["runs"] < inside.MIN_SAMPLES or found["total"] <= 0:
        return None
    return {scope: 100.0 * s / found["total"]
            for scope, s in sorted(found["seconds"].items())}


def hole(found: dict):
    """Percent of the runs' seconds in operations that no scope names or
    that found no instruction, or None under ``inside.MIN_SAMPLES`` runs."""
    share = shares(found)
    if share is None:
        return None
    return share.get(UNSCOPED, 0.0) + share.get(UNJOINED, 0.0)


# -- the program's records, and a run's summary -------------------------

def scope_maps():
    """The maps the program recorded of the executables it dispatched
    while the slice was traced, or None where it keeps none. Read after
    the engine has stopped (once a run: ``summary`` keeps what it made
    of them)."""
    from ray_tpu.util import tracing

    read = getattr(tracing, "recorded_scopes", None)
    return None if read is None else read()


def _record_seconds():
    """What the program says taking the maps cost it, or None."""
    from ray_tpu.util import tracing

    spans = tracing.recorded_spans("program.scopes")
    return sum(s["duration"] for s in spans) if spans else None


@functools.lru_cache(maxsize=1)
def summary(trace):
    """{"prefill": .., "decode": ..} (``scope_seconds`` of each kind of
    program) for a run's trace, or None with no trace or no map; says in
    one line every scope's share of each kind, the unjoined share, the
    share each rule of the program's reading inferred (``by_operand``,
    ``by_user``: part of the scopes' shares, not beside them), the
    operations that found their instruction, the number of maps, and
    ``prefill_shares=withheld`` where the prefill's ``hole`` passes
    ``HOLE``."""
    if trace is None or not trace.devices:
        return None
    try:
        maps = scope_maps()
        if not maps:
            say("scopes", maps=0 if maps is not None else "none-kept")
            return None
        out = {kind: scope_seconds(trace, maps, pattern)
               for kind, pattern in (("prefill", inside.PREFILL),
                                     ("decode", inside.DECODE))}
        facts = {"maps": len(maps), "record_s": _record_seconds()}
        for kind, found in out.items():
            facts.update({f"{kind}_runs": found["runs"],
                          f"{kind}_ops": found["ops"],
                          f"{kind}_ops_joined": found["joined"]})
            facts.update({f"{kind}.{scope or 'unscoped'}": share for
                          scope, share in (shares(found) or {}).items()})
            if found["runs"] >= inside.MIN_SAMPLES and found["total"] > 0:
                facts.update({f"{kind}.by_{rule}": 100.0 * s / found["total"]
                              for rule, s in sorted(found["inferred"].items())})
        if (hole(out["prefill"]) or 0.0) > HOLE:
            facts["prefill_shares"] = "withheld"
        say("scopes", **facts)
        return out
    except Exception as e:  # noqa: BLE001 - a record this cannot read
        say("scopes", unreadable=repr(e))
        return None


def prefill_share(trace, scopes: tuple):
    """The share of the prefill programs' time in operations under one of
    ``scopes``: 0.0 where the maps name nothing so (a measurement: the
    runs were joined and named), None without maps or runs enough, and
    None where more than ``HOLE`` percent of the prefill runs' own time is
    unnamed or unjoined: the maps are then another tree's, or the
    vocabulary has a hole, and a share of the rest would read low or high
    by as much."""
    found = summary(trace)
    gap = hole(found["prefill"]) if found else None
    if gap is None or gap > HOLE:
        return None
    share = shares(found["prefill"])
    return sum(share.get(scope, 0.0) for scope in scopes)


def unscoped_share(trace):
    """The LARGER of the prefill and the decode programs' ``hole``: the
    share of that kind's time in operations that no scope names or that
    found no instruction. The instrument's own gauge, of the kind that
    reads worst."""
    found = summary(trace)
    if not found:
        return None
    gaps = [gap for f in found.values() if (gap := hole(f)) is not None]
    return max(gaps) if gaps else None
