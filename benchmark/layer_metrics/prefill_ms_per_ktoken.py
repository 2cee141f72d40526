"""Per-layer metric ``prefill_ms_per_ktoken`` (PR 56): the prefill
programs' device time for a thousand prompt tokens computed. Over the
slice's ``engine.dispatch_prefill`` spans that have their ``device.run``
child (the watcher's stamps of the run's start and end on the host's
clock), the children's durations summed over the parents' ``new_tokens``.
Run and count come from ONE pair of spans, so the number does not swing
with how many cold prompts the slice happens to hold, as the programs'
share of the device's time does."""

from benchmark import inside, program_spans


def ms_per_ktoken(spans):
    """A pure function of span records. A dispatch whose child was dropped
    from the ring is skipped; None where the program records no spans, or
    under ``inside.MIN_SAMPLES`` dispatches, or no prompt token."""
    new_tokens = {s["span_id"]: s["attrs"]["new_tokens"] for s in spans or ()
                  if s["name"] == "engine.dispatch_prefill"
                  and "new_tokens" in s.get("attrs", {})}
    runs = [s for s in spans or () if s["name"] == "device.run"
            and s.get("parent_id") in new_tokens]
    tokens = sum(new_tokens[s["parent_id"]] for s in runs)
    if len(runs) < inside.MIN_SAMPLES or tokens <= 0:
        return None
    return sum(s["duration"] for s in runs) * 1e3 / (tokens / 1e3)


def read(run):
    return ms_per_ktoken(program_spans.engine_spans())
