"""A toy paged engine on the CPU and what the tests of its spans and
accounts read off it: the loop's idle point, the ring, ``stats()``'
account, the dispatch spans of requests served to their ends."""

import sys
import time
from functools import cache

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import llama
from ray_tpu.serve.paged_llm import PagedLLMEngine
from ray_tpu.util import tracing

PAGE = 16
# stats()' account of what the two programs computed (always on)
DECODE_ACCOUNT = ("decode_slot_steps", "decode_delivered",
                  "decode_overrun_tail", "decode_overrun_ahead",
                  "decode_vacant")
PREFILL_ACCOUNT = ("prefill_token_rows", "prefill_new_tokens")
# and of the ends it foresaw and the slots handed on ahead of a read-back
HANDOVER_ACCOUNT = ("retirements_foreseen", "slots_handed_over")
# two greedy answers part at a near tie of the model's own logits
NEAR_TIE = 0.1


@cache
def tiny_llama():
    """The tiny llama configuration and its weights."""
    cfg = llama.llama_tiny()
    return cfg, llama.init_params(cfg, jax.random.key(0))


def make_engine(tiny, **kwargs):
    cfg, params = tiny
    kwargs.setdefault("max_batch", 4)
    return PagedLLMEngine(cfg, params, max_len=128, page_size=PAGE,
                          num_pages=40, **kwargs)


def clear_ring():
    tracing.drain_spans(1 << 20)
    _, flight = tracing._rings()
    flight.clear()


def wait_idle(eng, timeout=60.0):
    """Until the loop thread stands in its idle poll: then every chunk it
    dispatched has been read back, and the counters and the ring rest."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        frame = sys._current_frames().get(eng._thread.ident)
        while frame is not None:
            if frame.f_code.co_name == "_wait_idle":
                return
            frame = frame.f_back
        time.sleep(0.005)
    raise AssertionError("the engine loop did not go idle")


def account(eng) -> dict:
    stats = eng.stats()
    return {k: stats[k] for k in (DECODE_ACCOUNT + PREFILL_ACCOUNT
                                  + HANDOVER_ACCOUNT)}


def handed_over(spans) -> int:
    return sum(s["attrs"]["handed_over"] for s in spans
               if s["name"] == "engine.admit")


def chunk_emits(spans) -> list:
    return [s["attrs"] for s in spans if s["name"] == "engine.emit"
            and s["attrs"]["what"] == "chunk"]


def served(tiny, submit, trace=True, **engine):
    """Requests handed over BEFORE the loop starts (so what it admits
    when does not hang on the clock), served to their ends, with tracing
    on unless ``trace`` is false: (the chunks' emit spans in order, the
    engine's account, the hand-overs its ``engine.admit`` spans show)."""
    eng = make_engine(tiny, **engine)
    clear_ring()
    if trace:
        tracing.enable_tracing()
    try:
        reqs = submit(eng)
        eng.start()
        for r, n in reqs:
            assert len(list(r.tokens())) == n
        wait_idle(eng)
        eng.stop()
        spans = tracing.recorded_spans("engine.")
    finally:
        tracing.disable_tracing()
        clear_ring()
    return chunk_emits(spans), account(eng), handed_over(spans)


def pool_stats(model, cfg, **engine):
    eng = PagedLLMEngine(cfg, model.init_params(cfg, jax.random.key(0)),
                         max_batch=3, max_len=128, page_size=PAGE,
                         num_pages=30, **engine)
    return eng, eng.stats()


def decode_spans_of(eng, prompts, new_tokens=9):
    """The ``engine.dispatch_decode`` spans of ``prompts`` served one
    after another, and the engine's ``stats()`` after them."""
    clear_ring()
    tracing.enable_tracing()
    try:
        eng.start()
        for prompt in prompts:
            assert len(list(eng.submit(
                prompt, max_new_tokens=new_tokens).tokens())) == new_tokens
        eng.stop()
        decodes = tracing.recorded_spans("engine.dispatch_decode")
    finally:
        tracing.disable_tracing()
        clear_ring()
    assert eng.error is None
    return decodes, eng.stats()


def note_engine_on(monkeypatch, backend, **changes):
    """An engine over the tiny latent plan (``changes`` to its
    configuration; pages of 128, a table of two) that FINDS ``backend``
    as it is built; its programs still lower for the CPU."""
    from ray_tpu.models import dots3_note
    from ray_tpu.serve import engine_programs

    cfg = dots3_note.dots3_note_tiny(**changes)
    monkeypatch.setattr(engine_programs.jax, "default_backend",
                        lambda: backend)
    eng = PagedLLMEngine(cfg, dots3_note.init_params(cfg, jax.random.key(0)),
                         max_batch=2, max_len=256, page_size=128,
                         num_pages=8)
    monkeypatch.undo()
    return eng


def same_greedy_choice(model, cfg, params, prompt, got, want):
    """Whether two greedy answers to ``prompt`` are the same tokens, or
    part at a near tie by the model's own logits (where two roundings of
    one computation may choose differently)."""
    if got == want:
        return True
    at = next(i for i, (a, b) in enumerate(zip(got, want)) if a != b)
    seq = jnp.asarray(list(prompt) + got[:at], jnp.int32)[None]
    logits = np.asarray(model.forward(cfg, params, seq)[0, -1], np.float32)
    return abs(logits[got[at]] - logits[want[at]]) < NEAR_TIE
