"""The join *scope -> compiled instruction -> trace event* (PR 57): the
arithmetic of ``benchmark/program_scopes.py`` on synthetic traces and
maps, the five readers built on it, and their entries in
``BENCHMARK.json``."""

import pytest

from benchmark import harness, inside, program_scopes
from benchmark.program_scopes import UNJOINED, UNSCOPED
from benchmark.trace import Trace

import bench_pins

PREFILL, DECODE = "jit_paged_prefill_w4", "jit_paged_decode_c16_w4"
ROUTED_CELLS = ["serve-moe-gen", "serve-code-gen", "serve-note-gen",
                "serve-reason-gen", "serve-brief-gen"]
BACKLOG_CELLS = ["serve-doc", "serve-moe-gen", "serve-code-gen",
                 "serve-instruct-gen", "serve-note-gen", "serve-reason-gen",
                 "serve-brief-gen"]
ENTRIES = {"prefill_routed_share": ("prefill program", ROUTED_CELLS),
           "prefill_combine_share": ("prefill program", ROUTED_CELLS),
           "prefill_attn_share": ("prefill program", BACKLOG_CELLS),
           "prefill_dense_share": ("prefill program", BACKLOG_CELLS),
           "unscoped_share": ("device programs", BACKLOG_CELLS)}

# two executables of ONE program name (a cold ``1 x 512`` and a suffix
# ``2 x 64``) whose instruction names collide and whose shapes do not
COLD = {"fusion.1": ["bf16[512,64]", "attn_qkv"],
        "paged_prefill_attn.2": ["bf16[1,512,64]", "attn"],
        "fusion.3": ["f32[1024,64]", "moe_combine"],
        "grouped_expert_ffn.4": ["bf16[1024,32]", "moe_experts"],
        "fusion.5": ["s32[1024]", "moe_dispatch"],
        "fusion.6": ["f32[512,8]", "moe_router"],
        "copy.7": ["bf16[512,64]", ""],
        "while.8": ["s32[]", ""]}
SUFFIX = {"fusion.1": ["bf16[128,64]", "attn_qkv"],
          "fusion.2": ["f32[2,4,64,512]", "attn"],
          "fusion.3": ["f32[8,128,32]", "moe_experts"],
          "fusion.5": ["bf16[128,64]", "shared_expert"],
          "fusion.6": ["f32[128,8]", "moe_router"]}
STEP = {"fusion.1": ["bf16[4,1,192]", "attn_qkv"],
        "paged_decode_attn.2": ["bf16[4,4,16]", "attn"],
        "fusion.3": ["f32[8,4,32]", "moe_experts"],
        "fusion.4": ["bf16[4,1,64]", "ffn"]}


def record(program, scopes, executable="x", inferred=None):
    return {"program": program, "executable": executable, "scopes": scopes,
            "inferred": inferred or {}}


# the cold executable's combine and dispatch took their scopes from an
# operand and from a user; nothing else is inferred
MAPS = [record(PREFILL, COLD, "cold", {"fusion.3": "operand",
                                       "fusion.5": "user"}),
        record(PREFILL, SUFFIX, "suffix"), record(DECODE, STEP, "step")]


def event(name, scopes, start, ms):
    shape = scopes[name][0]
    opcode = ("while" if name.startswith("while") else
              "custom-call" if "." in name and not name.startswith(
                  ("fusion", "copy")) else name.split(".")[0])
    return (f"%{name} = {shape}{{1,0:T(8,128)}} {opcode}(bf16[8]{{0}} %p)",
            start, start + ms * 1e-3)


def run(program, number, scopes, start, ops):
    """A program's run from ``start`` and its operations one behind the
    other: ``ops`` is [(instruction, ms)]; a loop's event spans the lot."""
    module, events, at = None, [], start
    for name, ms in ops:
        if name.startswith("while"):
            events.append(event(name, scopes, start, ms))
            continue
        events.append(event(name, scopes, at, ms))
        at += ms * 1e-3
    module = (f"{program}({number})", start, at + 1e-4)   # a gap at the end
    return module, events


COLD_OPS = [("fusion.1", 10), ("paged_prefill_attn.2", 20), ("fusion.6", 2),
            ("fusion.5", 8), ("grouped_expert_ffn.4", 26), ("fusion.3", 24),
            ("copy.7", 10), ("while.8", 100)]
SUFFIX_OPS = [("fusion.1", 2), ("fusion.2", 3), ("fusion.6", 1),
              ("fusion.3", 3), ("fusion.5", 1)]
STEP_OPS = [("fusion.1", 1), ("paged_decode_attn.2", 2), ("fusion.3", 4),
            ("fusion.4", 1)]


def trace_of(*runs):
    modules = [m for m, _ in runs]
    ops = [e for _, events in runs for e in events]
    return Trace([{"modules": modules, "ops": ops, "async_ops": []}], [],
                 extent_s=10.0)


def slice_of(cold=3, suffix=3, steps=5):
    """A slice of ``cold`` cold prefill runs, ``suffix`` suffix runs of the
    same program name and ``steps`` decode runs, a second apart."""
    runs = [run(PREFILL, 111, COLD, float(i), COLD_OPS)
            for i in range(cold)]
    runs += [run(PREFILL, 222, SUFFIX, 10.0 + i, SUFFIX_OPS)
             for i in range(suffix)]
    runs += [run(DECODE, 333, STEP, 20.0 + i, STEP_OPS)
             for i in range(steps)]
    return trace_of(*runs)


def test_an_events_name_gives_its_instruction_and_shape():
    assert program_scopes.instruction(
        "%fusion.563 = f32[64,32,768]{2,1,0:T(8,128)} fusion(bf16[64] %p)"
    ) == ("fusion.563", "f32[64,32,768]")
    # a tuple's first shape, as the program's record keeps it
    assert program_scopes.instruction(
        "%copy-start.3 = (s32[3]{0:T(128)}, s32[3]{0}, u32[]) copy-start(%x)"
    ) == ("copy-start.3", "s32[3]")
    assert program_scopes.instruction("no text") == ("no text", "")


def test_two_executables_of_one_name_are_told_apart_by_shape():
    """``fusion.1``, ``.3``, ``.5`` and ``.6`` are instructions of both
    prefill executables: each run takes the map in which EVERY operation
    of it has its shape, and so its own scopes."""
    found = program_scopes.scope_seconds(slice_of(), MAPS, inside.PREFILL)
    assert found["runs"] == 6 and found["ops"] == found["joined"] == 36
    seconds = found["seconds"]
    assert seconds[UNJOINED] == 0
    # the cold runs' combine and dispatch; the suffix runs have neither
    assert seconds["moe_combine"] == pytest.approx(3 * 0.024)
    assert seconds["moe_dispatch"] == pytest.approx(3 * 0.008)
    # ``fusion.3`` is the combine in one executable, the experts in the other
    assert seconds["moe_experts"] == pytest.approx(3 * 0.026 + 3 * 0.003)
    assert seconds["shared_expert"] == pytest.approx(3 * 0.001)
    assert seconds["attn"] == pytest.approx(3 * 0.020 + 3 * 0.003)
    assert seconds[UNSCOPED] == pytest.approx(3 * 0.010)     # ``copy.7``
    # the loop's event spans its body's: left out, as ``top_ops`` does
    assert sum(seconds.values()) == pytest.approx(3 * 0.100 + 3 * 0.010)
    assert found["total"] == pytest.approx(3 * 0.1001 + 3 * 0.0101)
    # what the program's one rule named, by the rule: part of the above
    assert found["inferred"] == {"operand": pytest.approx(3 * 0.024),
                                 "user": pytest.approx(3 * 0.008)}
    # a record from before the rule was told apart says nothing of it
    bare = [{k: v for k, v in m.items() if k != "inferred"} for m in MAPS]
    found = program_scopes.scope_seconds(slice_of(), bare, inside.PREFILL)
    assert found["joined"] == 36 and not found["inferred"]


def test_a_run_no_map_fits_counts_as_unjoined():
    """An executable the program recorded nothing of (or one compiled
    since): all of its operations are unjoined, the other runs keep
    theirs."""
    only_suffix = [MAPS[1], MAPS[2]]
    found = program_scopes.scope_seconds(slice_of(), only_suffix,
                                         inside.PREFILL)
    assert found["joined"] == 15 and found["ops"] == 36
    assert found["seconds"][UNJOINED] == pytest.approx(3 * 0.100)
    assert found["seconds"]["moe_combine"] == 0
    # two maps that fit and DISAGREE on a scope single nothing out
    twin = dict(SUFFIX, **{"fusion.2": ["f32[2,4,64,512]", "index_select"]})
    found = program_scopes.scope_seconds(
        slice_of(cold=0), [MAPS[1], record(PREFILL, twin, "twin")],
        inside.PREFILL)
    assert found["joined"] == 0
    # two that fit and agree (one program compiled twice) count as one
    found = program_scopes.scope_seconds(
        slice_of(cold=0), [MAPS[1], record(PREFILL, dict(SUFFIX), "again")],
        inside.PREFILL)
    assert found["joined"] == found["ops"] == 15


def test_a_run_the_slice_cut_takes_the_map_of_its_whole_runs():
    """The slice's edge cut the first run of an executable down to one
    operation, which BOTH maps of the name know by that shape: the choice
    is made on the fullest run of the ``jit_x(<number>)`` and holds for
    the cut one."""
    cut = run(PREFILL, 222, SUFFIX, 5.0, [("fusion.6", 1)])
    ambiguous = dict(COLD, **{"fusion.6": ["f32[128,8]", "moe_router"]})
    maps = [record(PREFILL, ambiguous, "cold"), MAPS[1]]
    whole = [run(PREFILL, 222, SUFFIX, 10.0 + i, SUFFIX_OPS)
             for i in range(4)]
    found = program_scopes.scope_seconds(trace_of(cut, *whole), maps,
                                         inside.PREFILL)
    assert found["joined"] == found["ops"] == 21
    assert found["seconds"]["shared_expert"] == pytest.approx(4 * 0.001)


def test_a_stray_operation_costs_itself_and_not_its_run():
    """One event in a hundred whose shape the map reads otherwise (or
    that it does not hold) is unjoined by itself: the run keeps its
    executable. More than that, and the map is another program's."""
    many = [("fusion.1", 1)] * 120
    odd = ("%fusion.1 = f32[9,9]{1,0} fusion(%p)", 0.05, 0.051)
    module, events = run(PREFILL, 222, SUFFIX, 0.0, many)
    found = program_scopes.scope_seconds(
        trace_of((module, events + [odd])), MAPS, inside.PREFILL)
    assert (found["ops"], found["joined"]) == (121, 120)
    assert found["seconds"][UNJOINED] == pytest.approx(0.001)
    found = program_scopes.scope_seconds(
        trace_of((module, events + [odd] * 3)), MAPS, inside.PREFILL)
    assert found["joined"] == 0


def test_an_operation_outside_every_run_is_left_out():
    lone = [event("fusion.1", COLD, 50.0, 5)]
    trace = slice_of()
    trace.devices[0]["ops"] += lone
    found = program_scopes.scope_seconds(trace, MAPS, inside.PREFILL)
    assert found["ops"] == 36
    # an operation that starts in a run and ends behind it is cut there
    module, events = run(PREFILL, 111, COLD, 60.0, [("fusion.1", 10)])
    name, start, _ = events[0]
    found = program_scopes.scope_seconds(
        trace_of((module, [(name, start, start + 1.0)])), MAPS,
        inside.PREFILL)
    assert found["seconds"]["attn_qkv"] == pytest.approx(module[2] - start)


@pytest.fixture
def traced(monkeypatch):
    """``program_scopes`` with the program's records stood in for: a
    callable that sets them, and the run's summary forgotten."""
    def use(maps):
        program_scopes.summary.cache_clear()
        monkeypatch.setattr(program_scopes, "scope_maps", lambda: maps)
    monkeypatch.setattr(program_scopes, "_record_seconds", lambda: 0.25)
    yield use
    program_scopes.summary.cache_clear()


class Run:
    def __init__(self, trace):
        self.trace = trace


def read(name, trace):
    return harness.load_reader(name)(Run(trace))


def test_the_five_readers_sum_their_scopes_over_the_prefill_runs(traced,
                                                                 capsys):
    traced(MAPS)
    trace = slice_of()
    total = 3 * 0.1001 + 3 * 0.0101
    routed = 3 * (0.002 + 0.008 + 0.026 + 0.024) + 3 * (0.001 + 0.003 + 0.001)
    assert read("prefill_routed_share", trace) == pytest.approx(
        100 * routed / total)
    assert read("prefill_combine_share", trace) == pytest.approx(
        100 * 3 * (0.008 + 0.024) / total)
    assert read("prefill_attn_share", trace) == pytest.approx(
        100 * (3 * 0.020 + 3 * 0.003) / total)
    assert read("prefill_dense_share", trace) == pytest.approx(
        100 * (3 * 0.010 + 3 * 0.002) / total)
    # the LARGER of the two kinds': the prefill runs' ``copy.7`` (the
    # decode runs hold nothing unnamed, and do not dilute it)
    assert read("unscoped_share", trace) == pytest.approx(
        100 * 3 * 0.010 / total)
    # one line for the five, with each kind's scopes apart
    lines = [line for line in capsys.readouterr().out.splitlines()
             if line.startswith("bench scopes:")]
    assert len(lines) == 1
    for word in ("maps=3", "record_s=0.2500", "prefill_runs=6",
                 "decode_runs=5", "prefill_ops_joined=36",
                 "prefill.moe_combine=", "prefill.unscoped=",
                 "prefill.by_operand=", "prefill.by_user=",
                 "decode.attn=", "decode.ffn="):
        assert word in lines[0], word
    for word in ("decode.moe_combine", "decode.by_", "withheld"):
        assert word not in lines[0], word


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_a_reader_is_none_under_min_samples_and_without_a_map(traced, name):
    assert inside.MIN_SAMPLES == 5
    traced(MAPS)
    assert read(name, slice_of(cold=2, suffix=2, steps=0)) is None
    for nothing in (None, []):          # a program from before PR 57; an
        traced(nothing)                 # engine that dispatched untraced
        assert read(name, slice_of()) is None
    traced(MAPS)
    assert read(name, None) is None                 # an untraced run
    assert read(name, Trace([], [])) is None        # no chip in the trace


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_a_reader_never_raises(traced, name, capsys):
    """A record of another shape, a map of another engine's programs and
    an event name no parser knows each give None or a share, and a word
    in the line: a traced run keeps its result line."""
    traced([{"program": PREFILL}])                      # no ``scopes``
    assert read(name, slice_of()) is None
    assert "unreadable=" in capsys.readouterr().out
    traced([record("jit_other", COLD)])
    share = read(name, slice_of())
    # (every operation unjoined; the gap at each run's end is in no share)
    assert share == (pytest.approx(100 * 0.330 / 0.3306)
                     if name == "unscoped_share" else None)
    assert "prefill_shares=withheld" in capsys.readouterr().out
    trace = slice_of()
    trace.devices[0]["ops"].append(("", 0.5, 0.6))
    trace.devices[0]["ops"].append(("%x.1 = odd text (", 1.5, 1.6))
    traced(MAPS)
    assert read(name, trace) is not None


def test_where_nothing_lies_under_a_readers_scopes_it_reads_zero(traced):
    """Runs that were joined and named and hold nothing under a reader's
    names read 0.0, a measurement (a dense model's prefill has no routed
    piece): a listed cell's line does not lose the entry to what the
    slice happened to hold."""
    dense = {k: v for k, v in COLD.items() if not v[1].startswith("moe")}
    ops = [(name, 1 if name == "copy.7" else ms) for name, ms in COLD_OPS
           if name in dense]
    traced([record(PREFILL, dense)])
    trace = trace_of(*[run(PREFILL, 111, dense, float(i), ops)
                       for i in range(5)])
    assert read("prefill_routed_share", trace) == 0.0
    assert read("prefill_combine_share", trace) == 0.0
    assert read("prefill_attn_share", trace) > 0


def test_maps_of_another_trees_executables_withhold_the_prefill_shares(
        traced, capsys):
    """A warm compile cache hands a prefill program without a kernel back
    as the tree that compiled its text first named it: here the suffix
    executable as the parent of PR 57 did, ``moe_router`` and
    ``moe_experts`` and nothing else. Every event still joins. The
    prefill runs' own unnamed time passes ``HOLE``, so the four prefill
    shares are None (not the stale tree's numbers) with a word in the
    line, and ``unscoped_share`` reads the PREFILL's share, which the
    decode runs' weight in the slice would have hidden."""
    stale = {name: [shape, scope if scope in ("moe_router", "moe_experts")
                    else ""] for name, (shape, scope) in SUFFIX.items()}
    traced([MAPS[0], record(PREFILL, stale, "suffix"), MAPS[2]])
    trace = slice_of(cold=1, suffix=6, steps=200)
    found = program_scopes.summary(trace)
    assert found["prefill"]["joined"] == found["prefill"]["ops"]
    unnamed = 1 * 0.010 + 6 * 0.006
    prefill = 1 * 0.1001 + 6 * 0.0101
    assert program_scopes.hole(found["prefill"]) == pytest.approx(
        100 * unnamed / prefill)
    assert program_scopes.hole(found["decode"]) == 0.0
    for name in sorted(ENTRIES):
        share = read(name, trace)
        assert share == (pytest.approx(100 * unnamed / prefill)
                         if name == "unscoped_share" else None), name
    # pooled with decode the same time would have read under 3
    assert 100 * unnamed / (prefill + 200 * 0.0081) < 3 < share
    assert "prefill_shares=withheld" in capsys.readouterr().out
    # this tree's own maps of the same slice: the shares are back
    traced(MAPS)
    assert read("prefill_routed_share", trace) > 0
    assert read("unscoped_share", trace) == pytest.approx(
        100 * 0.010 / prefill)


def test_the_accessor_reads_the_programs_own_records(monkeypatch):
    """``scope_maps`` is ``tracing.recorded_scopes()``; a program that
    has no such accessor keeps no map."""
    from ray_tpu.util import tracing

    monkeypatch.setattr(tracing, "recorded_scopes", lambda: MAPS,
                        raising=False)
    assert program_scopes.scope_maps() is MAPS
    monkeypatch.delattr(tracing, "recorded_scopes")
    assert program_scopes.scope_maps() is None


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_the_entry_is_appended_with_its_cells(bench, name):
    layer, cells = ENTRIES[name]
    own = bench_pins.entry(bench["per_layer"], name)
    assert own == {"name": name, "unit": "%", "better": "lower",
                   "source": "device_trace", "layer": layer,
                   "moves": "serve_tokens_per_s",
                   "workloads": own["workloads"]}
    assert own["workloads"][:len(cells)] == cells
    for cell in cells:
        bench_pins.reports(bench, cell, [name], moves="serve_tokens_per_s")
    # a layer's name is one the benchmark already gives that layer
    assert layer in {m["layer"] for m in bench["per_layer"]
                     if m["name"] not in ENTRIES}
