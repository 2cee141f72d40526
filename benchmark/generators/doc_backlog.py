"""An offline backlog: documents each asked several times with different
questions, in an order that lets the askings of one document share its
pages. Lengths come from quantile grids that the seed permutes, one grid
per cycle of ``docs_per_cycle`` documents; cycles repeat (new tokens, new
permutation, same multiset) for as long as the runner asks, so a faster
system never runs the backlog dry.

Order inside a wave of ``wave_docs`` documents: the first asking of each,
then the second of each, then the third: the askings of one document are
``wave_docs`` positions apart, far enough for the earlier one's prefill to
have been dispatched (its pages register then) and near enough for it to
be still alive, so the pages are never evicted in between."""

from __future__ import annotations

import numpy as np

from benchmark.generators.grid import Req, grid, permuted, rng_for


class Backlog:
    def __init__(self, p: dict, vocab: int, seed: int):
        self.p, self.vocab, self.seed = p, vocab, seed
        self.cycle = 0
        self.made = 0
        self._buf: list = []

    def _make_cycle(self):
        p = self.p
        rng = rng_for(self.seed, 4, self.cycle)
        n, k = p["docs_per_cycle"], p["askings"]
        docs = permuted(rng, grid(p["doc_tokens"], n))
        qs = permuted(rng, grid(p["question_tokens"], n * k))
        ans = permuted(rng, grid(p["answer_tokens"], n * k))
        out = []
        for w0 in range(0, n, p["wave_docs"]):
            wave = range(w0, min(w0 + p["wave_docs"], n))
            texts = {d: rng.integers(1, self.vocab, docs[d], dtype=np.int32)
                     for d in wave}
            for a in range(k):
                for d in wave:
                    q = rng.integers(1, self.vocab, qs[d * k + a],
                                     dtype=np.int32)
                    out.append(Req(
                        rid=self.made, prompt=np.concatenate([texts[d], q]),
                        max_new_tokens=ans[d * k + a],
                        session=self.cycle * n + d, turn=a))
                    self.made += 1
        self.cycle += 1
        return out

    def take(self, n: int) -> list:
        n = max(0, n)
        while len(self._buf) < n:
            self._buf.extend(self._make_cycle())
        out, self._buf = self._buf[:n], self._buf[n:]
        return out

    def first_cycle(self) -> list:
        """One cycle, which holds every length the grid has, without
        taking it."""
        n = self.p["docs_per_cycle"] * self.p["askings"]
        while len(self._buf) < n:
            self._buf.extend(self._make_cycle())
        return self._buf[:n]


def build(p: dict, model: dict, system: dict, seed: int, seconds: float):
    return {"backlog": Backlog(p, model["vocab_size"], seed),
            "ramp_s": p["ramp_s"]}
