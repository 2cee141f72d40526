"""The serving engine's host-side API: requests, stage metrics, named
programs, and the Serve deployment body.

The reference serves models via user code inside Serve replicas
(`python/ray/serve/_private/replica.py`, SURVEY.md P15) — it has no model
engine. The engine itself is ``PagedLLMEngine`` (``serve/paged_llm.py``:
continuous batching over a paged KV pool); this module holds what a
caller of it touches:

- ``Request``: what ``submit`` returns. Tokens stream back through its
  queue; its stamps split the time to first token into five stages
  (``Request.breakdown``), published as the ``ray_tpu_serve_stage_s``
  histogram so Serve autoscaling can act on queue depth and latency.
- ``_named_jit``: every program the engine compiles carries its static
  facts in its name.
- ``LLMDeployment``: the body ``@serve.deployment`` wraps; each replica
  owns one engine (and its chip).

Threading: one engine thread owns the device loop (prefill/decode); callers
enqueue requests and read token queues — no JAX calls on caller threads.
"""

from __future__ import annotations

import queue
import time
from dataclasses import dataclass, field
from typing import Iterator

import jax
import numpy as np

from ray_tpu.util import metrics as _metrics

# Per-request TTFT decomposition (metrics plane): every request's time to
# first token splits into queue_wait (submit -> prefill dispatch),
# device_wait (dispatch -> the device starts the prefill: the wait on its
# queue behind the decode chunk in flight), prefill (the program's own
# run; start and end stamped by the watcher thread), pipeline_stall
# (device completion -> the loop coming for the firsts) and ship (the
# host copy of the first-token batch). The five stages sum to the
# observed TTFT exactly (see Request.breakdown). Series carry the
# hosting deployment + replica tags (from the serve replica context) so
# the controller's autoscaler and the dashboard can split per
# deployment/replica; engines outside serve tag deployment="-".
_STAGES = ("queue_wait", "device_wait", "prefill", "pipeline_stall", "ship")
_serve_hist = _metrics.histogram(
    "ray_tpu_serve_stage_s", "per-request serve TTFT stage latency",
    tag_keys=("stage", "deployment", "replica"))


def _named_jit(name: str, fn, **jit_kwargs):
    """``jax.jit(fn)`` as the program ``jit_<name>``: a jitted
    ``functools.partial`` or lambda is ``jit__unknown`` / ``jit__lambda_``
    in a device trace, a compile log and the backend's list of live
    executables. The name carries the program's static facts (chunk,
    pages), so a reader tells the programs apart without looking inside
    them; it is also part of the compile-cache key."""
    def program(*args, **kwargs):
        return fn(*args, **kwargs)
    program.__name__ = program.__qualname__ = name
    return jax.jit(program, **jit_kwargs)


@dataclass
class Request:
    request_id: int
    prompt: np.ndarray                    # [P] int32
    max_new_tokens: int = 128
    temperature: float = 0.0
    eos_id: int | None = None
    # filled by the engine:
    out: "queue.Queue[int | None]" = field(default_factory=queue.Queue)
    submit_t: float = field(default_factory=time.monotonic)
    first_token_t: float | None = None
    # TTFT decomposition stamps (see Request.breakdown): prefill batch
    # dispatched / the device started it / its results ready (both from
    # the watcher thread) / the loop came to read the first-token batch
    dispatch_t: float | None = None
    start_t: float | None = None
    ready_t: float | None = None
    drain_t: float | None = None
    generated: int = 0
    slot: int = -1
    done: bool = False     # the engine has put its end-of-stream
    # set before the None sentinel when the request itself failed
    # (e.g. prompt longer than the cache) — distinguishes rejection from
    # a legitimate empty/EOS completion
    error: BaseException | None = None
    # tracing: the ambient span context at submit() (the replica's run
    # span when the request came through serve) plus a wall-clock submit
    # stamp — the engine emits its TTFT stage spans against these after
    # the first token drains
    trace_ctx: object | None = None
    submit_wall: float | None = None

    @property
    def ttft(self) -> float | None:
        if self.first_token_t is None:
            return None
        return self.first_token_t - self.submit_t

    @property
    def breakdown(self) -> dict | None:
        """Measured TTFT decomposition. ``start_t`` and ``ready_t``
        (stamped by the watcher thread off the device stream) are
        clamped into dispatch_t <= start <= ready <= first_token_t, so
        the five stages ALWAYS sum to the observed TTFT exactly. The
        loop usually comes for the firsts (``drain_t``) before they are
        ready and blocks: that wait is the device's (device_wait,
        prefill), not a stall of the pipeline, and ship starts when both
        the results and the loop are there."""
        if (self.first_token_t is None or self.dispatch_t is None
                or self.drain_t is None):
            return None
        ready = self.ready_t if self.ready_t is not None else self.drain_t
        ready = min(max(ready, self.dispatch_t), self.first_token_t)
        start = self.start_t if self.start_t is not None \
            else self.dispatch_t
        start = min(max(start, self.dispatch_t), ready)
        taken = min(max(self.drain_t, ready), self.first_token_t)
        return {
            "queue_wait_s": self.dispatch_t - self.submit_t,
            "device_wait_s": start - self.dispatch_t,
            "prefill_s": ready - start,
            "pipeline_stall_s": taken - ready,
            "ship_s": self.first_token_t - taken,
        }

    engine: "PagedLLMEngine | None" = None

    def tokens(self) -> Iterator[int]:
        """Blocking stream of generated token ids (ends on None sentinel).
        Raises the engine's error if its device loop died."""
        while True:
            tok = self.out.get()
            if tok is None:
                if self.error is not None:
                    raise self.error
                if self.engine is not None and self.engine.error is not None:
                    raise RuntimeError(
                        "LLM engine loop failed"
                    ) from self.engine.error
                return
            yield tok


class LLMDeployment:
    """Serve deployment body hosting a ``PagedLLMEngine`` in the replica
    process.

    Use with ``@serve.deployment``/`serve.run`; each replica owns its own
    engine (and TPU chip(s)). `model_builder` is a picklable zero-arg
    callable returning (cfg, params) — keeps weights out of the deploy RPC.
    ``engine_kwargs`` are the engine's own (``page_size``, ``num_pages``,
    ``kv_dtype`` ...).

        dep = serve.deployment(LLMDeployment).bind(model_builder=build)
        handle = serve.run(dep)
        tokens = handle.remote([1, 2, 3], max_new_tokens=16).result()
    """

    def __init__(self, model_builder, *, max_batch: int = 8,
                 max_len: int = 2048, **engine_kwargs):
        # here, not at the top: paged_llm imports this module's names
        from ray_tpu.serve.paged_llm import PagedLLMEngine

        cfg, params = model_builder()
        self._engine = PagedLLMEngine(
            cfg, params, max_batch=max_batch, max_len=max_len,
            **engine_kwargs)
        self._engine.start()

    def __call__(self, prompt, max_new_tokens: int = 128,
                 temperature: float = 0.0, eos_id: int | None = None):
        req = self._engine.submit(
            prompt, max_new_tokens=max_new_tokens,
            temperature=temperature, eos_id=eos_id)
        return list(req.tokens())

    def stats(self) -> dict:
        return self._engine.stats()
