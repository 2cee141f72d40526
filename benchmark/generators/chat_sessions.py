"""Multi-turn chat sessions on an open loop, fixed before the window.

The whole schedule (every request's prompt tokens, ``max_new_tokens`` and
due time) is made from ``--seed`` before anything runs. Session SHAPES
(turn count, user-message and answer lengths) come from the quantile grids
of the file's distributions through a fixed ``template_seed``: they are the
same for every ``--seed``. The seed permutes which session starts where,
which gap falls between which turns, which app a session talks to, and all
token ids. So every seed offers the same multiset of work.

Arrivals are NOT a Poisson process. Each session starts in a slot of its
own, ``period / sessions`` wide, at a random point of it: a jittered regular
grid, which has far fewer bursts of starts than starts placed uniformly at
random would. The conditioning below smooths further. This was chosen so
that six seeds agree within the bounds (PR 22 was refused over this cell's
spread); the price is that the mix does not exercise the engine's
admission under bursts, which is left to a bursty mix (PERF.md, open
cells).

Turn k's prompt is turn k-1's prompt + a synthetic answer + a new user
message: reuse is of prompt pages, and nothing depends on what random
weights generate. A turn is due its predecessor's due time + a nominal
service time of the predecessor's answer + a think-time gap.

The timeline is a circle of ``ramp_s + seconds + tail_s``: a turn that
would fall past its end wraps to its start, as the turn of a session that
began before the schedule did ("under way"). ``prefill`` lists the prompts
such sessions already hold in the prefix cache when the schedule starts;
the runner puts them there during set-up. Load is therefore stationary
from the ramp through the drain.
"""

from __future__ import annotations

import numpy as np

from benchmark.generators.grid import Req, grid, permuted, rng_for

# How far the draw is conditioned, fixed for every mix this generator
# serves. Of PLACEMENTS layouts the seed draws, the one whose window holds
# most nearly its share of the work is kept; within a layout, sessions are
# spread over the start slots in BALANCE_STRATA classes of like work. What
# each buys, over 12 seeds (PERF.md, PR 23): requests due inside the window
# spread 6.6% with neither, 5.3% with the strata alone, 1.5% with the
# placements alone, 0.7% with both.
PLACEMENTS = 64
BALANCE_STRATA = 8


def session_shapes(p: dict, n_sessions: int) -> list:
    """[(user_lens, answer_lens)] per session: seed-independent."""
    rng = rng_for(p["template_seed"], 1)
    turns = permuted(rng, grid(p["turns"], n_sessions))
    planned = sum(turns)
    users = permuted(rng, grid(p["user_tokens"], planned))
    answers = permuted(rng, grid(p["answer_tokens"], planned))
    shapes, j = [], 0
    for n in turns:
        plen, us, ans = p["system_prompt_tokens"], [], []
        for u, a in zip(users[j:j + n], answers[j:j + n]):
            if plen + u + a > p["max_context_tokens"]:
                break           # the session ends before it would pass
            us.append(u)
            ans.append(a)
            plen += u + a
        j += n
        if not us:              # even one turn must fit: shortest grid point
            us, ans = [p["user_tokens"]["min"]], [p["answer_tokens"]["min"]]
        shapes.append((us, ans))
    return shapes


def balanced_slots(rng, shapes: list, strata: int):
    """Which start slot (position round the circle) each session gets.
    Sessions are ranked by their work and cut into ``strata`` classes;
    every run of ``strata`` neighbouring slots holds one session of each
    class, in an order and a choice the seed makes. Any arc of the circle,
    the window among them, then holds close to its share of the work; a
    free permutation moved the requests inside the window by +-5%."""
    n = len(shapes)
    work = [sum(us) + 4 * sum(ans) + 200 * len(us) for us, ans in shapes]
    ranked = sorted(range(n), key=lambda i: (work[i], i))
    size = -(-n // strata)
    classes = [list(rng.permutation(ranked[c * size:(c + 1) * size]))
               for c in range(strata)]
    slots = [0] * n
    pos = 0
    for _ in range(size):
        for c in rng.permutation(strata):
            if classes[c]:
                slots[classes[c].pop()] = pos
                pos += 1
    return slots


def layout(p: dict, shapes: list, rng, period: float) -> list:
    """Start of every session and due time of every turn, unwrapped:
    [(session, turn, time)]. Slots and jitter place the sessions; the gap
    grid, permuted, spaces the turns."""
    n = len(shapes)
    n_req = sum(len(us) for us, _ in shapes)
    slots = balanced_slots(rng, shapes, BALANCE_STRATA)
    jitter = rng.random(n)
    gaps = permuted(rng, grid(dict(p["gap_s"], real=True),
                              max(1, n_req - n)))
    out, g = [], 0
    for s, (us, ans) in enumerate(shapes):
        t = (slots[s] + jitter[s]) / n * period
        for k in range(len(us)):
            if k:
                t += (p["nominal_ttft_s"] + ans[k - 1] * p["nominal_tpot_s"]
                      + gaps[g])
                g += 1
            out.append((s, k, t))
    return out


def window_miss(p: dict, shapes: list, times: list, period: float,
                seconds: float) -> float:
    """How far the work due inside the window is from the window's share
    of the whole schedule's: requests, prompt tokens and answer tokens."""
    lo, hi = p["ramp_s"], p["ramp_s"] + seconds
    inside, total = [0.0, 0.0, 0.0], [0.0, 0.0, 0.0]
    for s, k, t in times:
        us, ans = shapes[s]
        plen = (p["system_prompt_tokens"] + sum(us[:k + 1]) + sum(ans[:k]))
        for i, x in enumerate((1.0, plen, ans[k])):
            total[i] += x
            if lo <= t % period < hi:
                inside[i] += x
    share = seconds / period
    return sum(abs(inside[i] / total[i] - share) / share for i in range(3))


def build(p: dict, model: dict, system: dict, seed: int, seconds: float):
    period = p["ramp_s"] + seconds + p["tail_s"]
    n_sessions = max(1, round(p["sessions_per_s"] * period))
    shapes = session_shapes(p, n_sessions)
    vocab = model["vocab_size"]

    # of PLACEMENTS layouts the seed draws, the one whose window holds most
    # nearly its share of requests and tokens: every seed then measures
    # the same amount of work to within a request or two
    times = min((layout(p, shapes, rng_for(seed, 2, a), period)
                 for a in range(PLACEMENTS)),
                key=lambda ts: window_miss(p, shapes, ts, period, seconds))
    due = {(s, k): t for s, k, t in times}
    rng = rng_for(seed, 5)
    apps = permuted(rng, grid(p["apps"], n_sessions))
    tok = rng_for(seed, 3)
    system_prompts = [tok.integers(1, vocab, p["system_prompt_tokens"],
                                   dtype=np.int32)
                      for _ in range(p["apps"]["n"])]

    reqs, prefill = [], []
    for s, (us, ans) in enumerate(shapes):
        prompt, wraps = system_prompts[apps[s]], 0
        for k, (u, a) in enumerate(zip(us, ans)):
            t = due[s, k]
            if k:
                answer = tok.integers(1, vocab, ans[k - 1], dtype=np.int32)
                if int(t // period) > wraps:
                    # this turn belongs to a session under way when the
                    # schedule starts: its last prompt is already cached
                    prefill.append((due[s, k - 1] - period * (wraps + 1),
                                    prompt))
                prompt = np.concatenate([prompt, answer])
            user = tok.integers(1, vocab, u, dtype=np.int32)
            prompt = np.concatenate([prompt, user])
            wraps = int(t // period)
            reqs.append(Req(rid=len(reqs), prompt=prompt, max_new_tokens=a,
                            due=t % period, session=s, turn=k))
    reqs.sort(key=lambda r: r.due)
    for i, r in enumerate(reqs):
        r.rid = i
    # oldest first, the apps' system prompts oldest of all: LRU order
    prefill.sort(key=lambda x: x[0])
    warm = list(system_prompts) + [pr for _, pr in prefill]
    return {"requests": reqs, "prefill": warm, "period_s": period,
            "ramp_s": p["ramp_s"], "tail_s": p["tail_s"],
            "cap_s": p["cap_s"]}
