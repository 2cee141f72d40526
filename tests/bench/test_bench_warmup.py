"""The warm-up grid: which cells a schedule reaches from its prompts alone,
and the request at the top of a cell. Pure arithmetic; the warm-up itself
runs in the harness tests, through the engine's public path."""

import numpy as np
import pytest

from benchmark import serving

SYSTEM = {"page_size": 128, "max_len": 2048}
LIMITS = {"max_group": 2, "max_score_elements": 8388608}


def prompt(n, seed=0, head=None):
    rng = np.random.default_rng(seed)
    body = rng.integers(1, 1000, n, dtype=np.int32)
    if head is not None:
        body[:len(head)] = head
    return body


def test_a_lone_prompt_reaches_its_cold_cell_at_each_group_size():
    prefill, decode = serving.warm_cells([(prompt(600), 40)], SYSTEM, LIMITS)
    assert prefill == {(1, 1024, 8), (2, 1024, 8)}
    assert decode == {8}            # 640 tokens: 5 pages and one more


def test_shared_pages_add_the_suffix_cells():
    system_prompt = prompt(512, seed=1)
    a = prompt(600, seed=2, head=system_prompt)
    b = prompt(700, seed=3, head=system_prompt)
    prefill, _ = serving.warm_cells([(a, 8), (b, 8)], SYSTEM, LIMITS)
    # cold, or the four shared pages cached: 88 and 188 new tokens
    assert {(1, 1024, 8), (1, 128, 8), (1, 256, 8)} <= prefill
    assert all(n in (1, 2) for n, _, _ in prefill)


def test_a_group_that_does_not_fit_is_left_out():
    prefill, decode = serving.warm_cells([(prompt(2000), 40)], SYSTEM,
                                         dict(LIMITS, max_group=4))
    assert (2, 2048, 16) in prefill and (4, 2048, 16) not in prefill
    assert decode == {16}


@pytest.mark.parametrize("t,wp,cached,new", [
    (1024, 8, 0, 1024),         # cold: the whole context is new
    (128, 8, 7, 128),           # a page of new tokens behind seven cached
    (16, 16, 15, 16),           # a short turn at the end of a long context
    (2048, 16, 0, 2047),        # max_len itself is refused: one fewer
    (256, 16, 14, 255)])
def test_the_top_of_a_cell_lies_in_the_cell(t, wp, cached, new):
    page, max_len = SYSTEM["page_size"], SYSTEM["max_len"]
    assert serving._cell_top(t, wp, page, max_len) == (cached, new)
    total = cached * page + new
    assert total < max_len and cached <= (total - 1) // page
    assert serving._ceil_pow2(new, 16) == t
    assert serving._ceil_pow2(-(-total // page)) == wp
