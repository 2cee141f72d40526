"""The spans the program's engine loop recorded of itself while the traced
slice's profiler session was live (PR 24: ``engine.*`` around the loop's
phases and ``device.run`` from its watcher thread, kept in the process's
span ring by ``ray_tpu.util.tracing``). With ``benchmark/systems.py`` the
only place the benchmark touches the program; the arithmetic on what it
returns is ``benchmark/inside.py``.

The trace cannot give them whole: ``trace.Trace.from_file`` keeps the
phases' names and times (they name the idle gaps of the breakdown), not
their counts or which span caused which. A program from before PR 24
records none and has no accessor: then this returns None and every
by-span reader with it."""

from __future__ import annotations

import functools

from benchmark import inside
from benchmark.harness import say


@functools.lru_cache(maxsize=1)
def engine_spans():
    """The loop's spans, oldest first, or None where the program keeps
    none. Read once, after the engine has stopped; says in one line what
    it found, and the medians of the five stages of the time to first
    token over the slice's requests beside the median of their sum."""
    from ray_tpu.util import tracing

    read = getattr(tracing, "recorded_spans", None)
    if read is None:
        return None
    spans = read("engine.") + read("device.")
    say("engine_spans", **inside.summary(spans))
    return spans
