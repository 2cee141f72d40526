"""The grouped expert kernel (``ops/grouped_expert_ffn.py``) on the CPU
(``interpret=True``) against a loop over the experts in float32: every
form, one layer of a run's stacks, groups that are empty, several tiles
long or everything, rows behind the last group, a last tile that is
partial; the walk's index arithmetic; the column blocks at the
benchmark's widths; the rule."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import grouped_expert_ffn as gef
from ray_tpu.ops import moe

TILE = 16


def _loop_over_experts(xs, load, gate, up, down, form="swiglu"):
    """Row i of group e through expert e, in float64; rows in no group
    zero."""
    xs, up, down = (np.asarray(a, np.float64) for a in (xs, up, down))
    out = np.zeros((xs.shape[0], down.shape[2]))
    start = 0
    for e, n in enumerate(np.asarray(load)):
        rows = xs[start:start + n]
        if gate is None:
            h = np.maximum(rows @ up[e], 0.0) ** 2
        else:
            g = rows @ np.asarray(gate, np.float64)[e]
            act = (np.maximum(g, 0.0) if form == "reglu"
                   else g / (1.0 + np.exp(-g)))
            h = act * (rows @ up[e])
        out[start:start + n] = h @ down[e]
        start += n
    return out


LAYERS, LAYER = 3, 1


def _stacks(form, experts, d, f, seed=0, dtype=jnp.float32):
    """The stacks of a run of ``LAYERS`` layers [L, H, .., ..]; the calls
    below are layer ``LAYER``'s, which ``_layer`` picks out."""
    ks = jax.random.split(jax.random.key(seed), 3)
    gate = (jax.random.normal(ks[0], (LAYERS, experts, d, f)) * d ** -0.5
            ).astype(dtype) if form != "relu2" else None
    up = (jax.random.normal(ks[1], (LAYERS, experts, d, f)) * d ** -0.5
          ).astype(dtype)
    down = (jax.random.normal(ks[2], (LAYERS, experts, f, d)) * f ** -0.5
            ).astype(dtype)
    return gate, up, down


def _layer(*stacks):
    return tuple(w if w is None else w[LAYER] for w in stacks)


# rows, the groups' sizes: what the sorted pairs of a call can look like
_LOADS = {
    "even-groups": (96, [16, 32, 16, 32]),
    "groups-that-straddle-tiles": (96, [5, 7, 30, 3, 21, 30]),
    "an-expert-with-no-row": (64, [10, 0, 22, 0, 0, 9, 23]),
    "one-of-several-tiles": (112, [3, 70, 5, 34]),
    "every-pair-on-one-expert": (80, [0, 0, 80, 0]),
    "absent-choices-and-padding-behind": (96, [9, 14, 0, 20]),
    "rows-not-a-multiple-of-the-tile": (75, [20, 33, 22]),
    "a-partial-last-tile-half-held": (75, [20, 30, 12]),
    "nothing-held": (48, [0, 0, 0]),
    "one-row": (32, [0, 1, 0]),
}


@pytest.mark.parametrize("form", moe.EXPERT_FORMS)
@pytest.mark.parametrize("case", _LOADS)
def test_the_kernel_against_a_loop_over_experts(form, case):
    rows, load = _LOADS[case]
    d, f = 128, 48          # the up stacks are read as they lie: [H, F, D]
    gate, up, down = _stacks(form, len(load), d, f)
    xs = jax.random.normal(jax.random.key(1), (rows, d))
    held = sum(load)
    # whatever lies behind the last group must reach no held row
    xs = xs.at[held:].set(jnp.nan)
    load = jnp.asarray(load, jnp.int32)
    act = {"gate_act": moe.EXPERT_FORMS[form]}
    got = gef.grouped_expert_ffn_kernel(xs, load, gate, up, down, LAYER,
                                        tile=TILE, interpret=True, **act)
    assert got.shape == (rows, d) and got.dtype == jnp.float32
    want = _loop_over_experts(xs, load, *_layer(gate, up, down),
                              form=form)[:held]
    np.testing.assert_allclose(np.asarray(got)[:held], want, rtol=2e-5,
                               atol=2e-5)
    # and the plain sorted formulation is the same function of its groups
    plain = gef.grouped_expert_ffn_reference(
        jnp.nan_to_num(xs), load, gate, up, down, LAYER, **act)
    np.testing.assert_allclose(np.asarray(plain)[:held], want, rtol=2e-5,
                               atol=2e-5)
    assert not np.asarray(plain)[held:].any()


@pytest.mark.parametrize("form", moe.EXPERT_FORMS)
def test_the_kernel_in_bf16_accumulates_in_float32(form):
    """bf16 rows and stacks, float32 sums and a bf16 hidden activation
    between the two calls: the plain formulation's arithmetic, to the
    order of a float32 sum."""
    rows, load = _LOADS["groups-that-straddle-tiles"]
    gate, up, down = _stacks(form, len(load), 64, 128, dtype=jnp.bfloat16)
    xs = jax.random.normal(jax.random.key(2), (rows, 64), jnp.bfloat16)
    load = jnp.asarray(load, jnp.int32)
    act = {"gate_act": moe.EXPERT_FORMS[form]}
    got = gef.grouped_expert_ffn_kernel(xs, load, gate, up, down, LAYER,
                                        tile=TILE, interpret=True, **act)
    want = gef.grouped_expert_ffn_reference(xs, load, gate, up, down, LAYER,
                                            **act)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)


def test_column_blocks_split_a_wide_stack(monkeypatch):
    """A stack wider than a step's block is walked a column block at a
    time, every visit again a block: two blocks of 128 of 256 columns
    here, a partial third of 320."""
    monkeypatch.setattr(gef, "WEIGHT_BLOCK_BYTES", 2 * 64 * 128 * 4)
    rows, load = _LOADS["an-expert-with-no-row"]
    load = jnp.asarray(load, jnp.int32)
    xs = jax.random.normal(jax.random.key(3), (rows, 64))
    for f in (256, 320):
        assert gef._column_block(64, f, 2, 4) == 128
        gate, up, down = _stacks("swiglu", load.shape[0], 64, f, seed=f)
        got = gef.grouped_expert_ffn_kernel.__wrapped__(
            xs, load, gate, up, down, LAYER, tile=TILE, interpret=True)
        want = _loop_over_experts(xs, load, *_layer(gate, up, down))
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5,
                                   atol=2e-5)


@pytest.mark.parametrize("widths,block", [
    # K, N, stacks -> columns a block, at the benchmark's four widths
    ((2688, 1856, 1), 1856), ((1856, 2688, 1), 2688),      # nano
    ((3072, 1024, 2), 1024), ((1024, 3072, 1), 3072),      # code
    ((5120, 1536, 2), 512), ((1536, 5120, 1), 2560),       # note
    ((2048, 1024, 2), 1024), ((1024, 2048, 1), 2048)])     # moe
def test_column_blocks_at_the_benchmarks_widths(widths, block):
    k, n, stacks = widths
    tn = gef._column_block(k, n, stacks, 2)
    assert tn == block and stacks * k * tn * 2 <= gef.WEIGHT_BLOCK_BYTES
    assert tn == n or (tn % 128 == 0 and n % tn == 0)


@pytest.mark.parametrize("seed", range(6))
def test_the_walk_visits_every_held_row_once_with_its_group(seed):
    rng = np.random.default_rng(seed)
    groups, tile = int(rng.integers(1, 9)), int(rng.choice([8, 16, 128]))
    load = rng.integers(0, 3 * tile, groups) * (rng.random(groups) < 0.7)
    rows = int(load.sum() + rng.integers(0, 2 * tile))
    if rows == 0:
        rows = tile
    offsets, group, tiles, count = (np.asarray(a) for a in gef.group_visits(
        jnp.asarray(load, jnp.int32), rows, tile))
    n_tiles = -(-rows // tile)
    assert group.shape == tiles.shape == (n_tiles + groups - 1,)
    assert np.array_equal(offsets, np.concatenate([[0], np.cumsum(load)]))
    count = int(count[0])
    assert count <= n_tiles + int((load > 0).sum()) - 1 or count == 0
    seen = np.full(rows, -1)
    for v in range(count):
        g, t = group[v], tiles[v]
        assert load[g] > 0 and 0 <= t < n_tiles
        mine = np.arange(t * tile, min((t + 1) * tile, rows))
        mine = mine[(mine >= offsets[g]) & (mine < offsets[g + 1])]
        assert mine.size and (seen[mine] == -1).all()
        seen[mine] = g
    held = int(load.sum())
    assert (seen[:held] == np.repeat(np.arange(groups), load)).all()
    assert (seen[held:] == -1).all()
    # in the sorted order, so that a tile's visits are consecutive and a
    # group's are too; the steps past the count repeat the last visit
    assert (np.diff(tiles[:count]) >= 0).all()
    assert (np.diff(group[:count]) >= 0).all()
    assert (group[count:] == group[max(count - 1, 0)]).all()
    assert (tiles[count:] == tiles[max(count - 1, 0)]).all()


def test_the_rule_is_the_traced_token_count_alone():
    """Past ``DENSE_MAX_TOKENS`` rows the sorted formulation, up to it
    every held expert over every row; and the line stands above the most
    slots any cell decodes with, so no decode program holds the kernel."""
    line = moe.DENSE_MAX_TOKENS
    assert [moe.expert_kernel_engages(r) for r in (1, line, line + 1, 4096)
            ] == [False, False, True, True]
    assert line >= 128


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("run", [False, True], ids=["one-layer", "a-run"])
@pytest.mark.parametrize("held", ["all", "share"])
@pytest.mark.parametrize("top_k", [6, 8, 10])
def test_the_grouped_rows_come_back_to_their_tokens(monkeypatch, top_k, held,
                                                    run, dtype, tol):
    """The sorted formulation (its rows gathered back with the choices on
    the major axis) against every held expert over every row, one choice
    for both: all 16 experts held or 5 of them from the fourth (so a part
    of every token's choices lies on absent experts, and ALL of token 0's
    do), padding rows among the tokens, the stacks one layer's or a run's.
    Padding rows and the token with no held choice come out exactly
    zero."""
    t, d, f, e = 40, 64, 32, 16
    first, h = (0, e) if held == "all" else (3, 5)
    ks = jax.random.split(jax.random.key(top_k), 3)
    x = jax.random.normal(ks[0], (t, d)).astype(dtype)
    vals, idx = jax.lax.top_k(jax.random.uniform(ks[1], (t, e)), top_k)
    if held == "share":     # token 0 chooses among the absent alone
        idx = idx.at[0].set((first + h + jnp.arange(top_k)) % e)
    valid = jnp.arange(t) % 7 != 3
    gate, up, down = (w[:, first:first + h]
                      for w in _stacks("swiglu", e, d, f, dtype=dtype))
    layer = LAYER if run else None
    if not run:
        gate, up, down = _layer(gate, up, down)
    out = {}
    for name, line in (("grouped", 0), ("every", 1 << 30)):
        monkeypatch.setattr(moe, "DENSE_MAX_TOKENS", line)
        out[name], load = moe.moe_experts(
            x, (vals, idx), gate, up, down, n_experts=e, first_expert=first,
            valid=valid, layer=layer)
        assert out[name].shape == (t, d) and out[name].dtype == dtype
    got, want = (np.asarray(out[n], np.float32) for n in ("grouped", "every"))
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
    assert np.abs(want).max() > 0.1 and int(load.sum()) > 0
    assert not got[~np.asarray(valid)].any()
    if held == "share":
        assert not got[0].any() and got[1:3].any()


def test_the_op_takes_the_kernels_branch_for_a_tpu_alone():
    """``moe_ffn_dropless`` past the line holds both lowerings of the
    sorted rows, chosen by the platform the program is lowered for: the
    kernel for a TPU, the plain loop elsewhere."""
    t = moe.DENSE_MAX_TOKENS + 8
    shapes = [jax.ShapeDtypeStruct(s, jnp.float32) for s in (
        (t, 64), (64, 8), (8, 64, 32), (8, 64, 32), (8, 32, 64))]
    text = str(jax.make_jaxpr(lambda *a: moe.moe_ffn_dropless(*a, top_k=3))(
        *shapes))
    assert "platform_index" in text
    assert text.count(gef.KERNEL_NAME) >= 2         # the two calls
    assert "ragged_dot" not in text
    under = str(jax.make_jaxpr(lambda *a: moe.moe_ffn_dropless(*a, top_k=3))(
        jax.ShapeDtypeStruct((moe.DENSE_MAX_TOKENS, 64), jnp.float32),
        *shapes[1:]))
    assert gef.KERNEL_NAME not in under and "platform_index" not in under
