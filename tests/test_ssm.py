"""``ops/ssm.py``: the chunked scan is the token-by-token recurrence from
any starting state, padding is exact (a position with a zero step leaves
the state bit for bit as it was), and the one-token update is one step of
the same recurrence. Tiny widths, float32, on the CPU. And the decode
step's update over the slots' STACKED states (``ssm_state_step``): its
Pallas kernel in interpret mode against the plain formulation, at tiny
and at the published head shapes, and the rule that chooses between
them."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import ssm
from ray_tpu.ops.ssm import causal_conv, last_rows, ssm_scan, ssm_step

HEADS, WIDTH, STATE, GROUPS = 6, 8, 16, 2


def draw(seed, rows, tokens, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(ks[0], (rows, tokens, HEADS, WIDTH)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (rows, tokens, HEADS)) - 2)
    a = -jnp.exp(jax.random.uniform(ks[2], (HEADS,), minval=0.0, maxval=2.5))
    b = jax.random.normal(ks[3], (rows, tokens, GROUPS, STATE)).astype(dtype)
    c = jax.random.normal(ks[4], (rows, tokens, GROUPS, STATE)).astype(dtype)
    s0 = jax.random.normal(ks[5], (rows, HEADS, WIDTH, STATE))
    return x, dt, a, b, c, s0


def token_by_token(x, dt, a, b, c, state):
    """The recurrence as written: S <- exp(dt a) S + dt x (x) b; y = S c,
    head j on group j // (H / G). NumPy, float64."""
    x, dt, a, b, c, state = (np.asarray(v, np.float64)
                             for v in (x, dt, a, b, c, state))
    per = HEADS // GROUPS
    ys = np.zeros(x.shape)
    for t in range(x.shape[1]):
        for h in range(HEADS):
            g = h // per
            decay = np.exp(dt[:, t, h] * a[h])[:, None, None]
            add = (dt[:, t, h, None] * x[:, t, h])[:, :, None] \
                * b[:, t, g][:, None, :]
            state[:, h] = decay * state[:, h] + add
            ys[:, t, h] = np.einsum("bpn,bn->bp", state[:, h], c[:, t, g])
    return ys, state


@pytest.mark.parametrize("tokens,chunk", [
    (1, 8), (5, 8), (8, 8), (24, 8), (64, 16), (256, 128)],
    ids=["one-token", "short-chunk", "one-chunk", "three-chunks",
         "four-chunks", "two-of-128"])
def test_chunked_scan_is_the_recurrence_from_any_state(tokens, chunk):
    """From a NON-ZERO state: a zero start would not show a carried state
    that is decayed wrongly or fed to the outputs at the wrong power.
    Tolerance: both sides compute the same sums, the scan in float32 in
    another order (a chunk's decays are exponentials of differences of a
    running sum, at most ``chunk`` terms long)."""
    x, dt, a, b, c, s0 = draw(tokens, 2, tokens)
    y, s1 = ssm_scan(x, dt, a, b, c, s0, chunk=chunk)
    want_y, want_s = token_by_token(x, dt, a, b, c, s0)
    assert y.dtype == s1.dtype == jnp.float32
    np.testing.assert_allclose(y, want_y, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(s1, want_s, rtol=2e-4, atol=2e-4)


def test_a_block_that_is_no_whole_number_of_chunks_is_refused():
    x, dt, a, b, c, s0 = draw(0, 1, 12)
    with pytest.raises(ValueError, match="whole number"):
        ssm_scan(x, dt, a, b, c, s0, chunk=8)


@pytest.mark.parametrize("valid", [1, 7, 8, 9, 23], ids=lambda n: f"len{n}")
def test_padding_leaves_the_state_bit_for_bit(valid):
    """A row of ``valid`` tokens padded to 24 with the step zeroed behind
    them ends in EXACTLY the state of the same tokens scanned alone where
    the padding lies in chunks of its own, and the outputs at the valid
    positions are the same bits: ``exp(0 a) = 1`` and ``0 x (x) b = 0``,
    whatever lies in the padded positions."""
    x, dt, a, b, c, s0 = draw(valid, 2, 24)
    mask = (jnp.arange(24) < valid)[None, :, None]
    y_pad, s_pad = ssm_scan(x, jnp.where(mask, dt, 0.0), a, b, c, s0, chunk=8)
    # the same rows with garbage where the padding is
    junk = 1e3 * jnp.ones_like(x)
    x2 = jnp.where(mask[..., None], x, junk)
    y2, s2 = ssm_scan(x2, jnp.where(mask, dt, 0.0), a, b, c, s0, chunk=8)
    np.testing.assert_array_equal(np.asarray(s_pad), np.asarray(s2))
    np.testing.assert_array_equal(np.asarray(y_pad[:, :valid]),
                                  np.asarray(y2[:, :valid]))
    want_y, want_s = token_by_token(x[:, :valid], dt[:, :valid], a,
                                    b[:, :valid], c[:, :valid], s0)
    np.testing.assert_allclose(s_pad, want_s, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(y_pad[:, :valid], want_y, rtol=2e-4, atol=2e-4)
    if valid % 8 == 0:
        # whole chunks of padding: the carried state passes through them
        # untouched, bit for bit
        _, s_cut = ssm_scan(x[:, :valid], dt[:, :valid], a, b[:, :valid],
                            c[:, :valid], s0, chunk=8)
        np.testing.assert_array_equal(np.asarray(s_pad), np.asarray(s_cut))


def test_a_row_of_padding_alone_returns_its_state_untouched():
    x, dt, a, b, c, s0 = draw(3, 2, 16)
    y, s1 = ssm_scan(x, jnp.zeros_like(dt), a, b, c, s0, chunk=8)
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s0))


def test_the_step_is_one_token_of_the_recurrence():
    x, dt, a, b, c, s0 = draw(5, 3, 4)
    state = s0
    for t in range(4):
        y, state = ssm_step(x[:, t], dt[:, t], a, b[:, t], c[:, t], state)
        want_y, want_s = token_by_token(x[:, :t + 1], dt[:, :t + 1], a,
                                        b[:, :t + 1], c[:, :t + 1], s0)
        np.testing.assert_allclose(y, want_y[:, t], rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(state, want_s, rtol=1e-5, atol=1e-5)
    # a zero step moves nothing
    _, same = ssm_step(x[:, 0], jnp.zeros_like(dt[:, 0]), a, b[:, 0],
                       c[:, 0], state)
    np.testing.assert_array_equal(np.asarray(same), np.asarray(state))


def test_bf16_operands_keep_a_float32_state():
    """In a served model x, b and c are bf16; the state, the step and the
    outputs stay float32, and the scan stays within bf16's rounding of
    its operands of the float64 recurrence on the same bf16 values."""
    x, dt, a, b, c, s0 = draw(9, 2, 32, jnp.bfloat16)
    y, s1 = ssm_scan(x, dt, a, b, c, s0, chunk=8)
    assert y.dtype == s1.dtype == jnp.float32
    want_y, want_s = token_by_token(*(v.astype(jnp.float32)
                                      for v in (x, dt, a, b, c, s0)))
    assert np.abs(np.asarray(y) - want_y).max() < 0.02 * np.abs(want_y).max()
    assert np.abs(np.asarray(s1) - want_s).max() < 0.02 * np.abs(want_s).max()


def test_causal_conv_and_the_tail_a_row_leaves():
    """Position i sees i-3..i, zeros (or the given tail) before the row's
    start; ``last_rows`` hands back what a convolution needs to go on
    from each row's length, which for a row shorter than the filter
    reaches back into the tail it was given."""
    rows, tokens, width, taps = 2, 9, 5, 4
    ks = jax.random.split(jax.random.key(1), 4)
    x = jax.random.normal(ks[0], (rows, tokens, width))
    tail = jax.random.normal(ks[1], (rows, taps - 1, width))
    w = jax.random.normal(ks[2], (width, taps))
    bias = jax.random.normal(ks[3], (width,))
    got = causal_conv(x, tail, w, bias)
    seq = np.concatenate([np.asarray(tail), np.asarray(x)], axis=1)
    want = np.stack([
        sum(seq[:, i + k] * np.asarray(w)[:, k] for k in range(taps))
        for i in range(tokens)], axis=1) + np.asarray(bias)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert causal_conv(x, tail, w, None).shape == (rows, tokens, width)
    # one convolution over the row is the convolution over its halves,
    # the second from the tail the first leaves
    first = causal_conv(x[:, :5], tail, w, bias)
    carried = last_rows(x[:, :5], tail, jnp.array([5, 5]))
    second = causal_conv(x[:, 5:], carried, w, bias)
    np.testing.assert_allclose(jnp.concatenate([first, second], 1), got,
                                rtol=1e-5, atol=1e-5)
    lengths = jnp.array([2, 9])
    kept = np.asarray(last_rows(x, tail, lengths))
    np.testing.assert_array_equal(kept[0], seq[0, 2:5])    # tail[2], x[0:2]
    np.testing.assert_array_equal(kept[1], seq[1, 9:12])   # x[6:9]


# -- the decode step's update over the slots' stacked states -----------------

# layers, slots, heads, head width, state size, groups
_TINY = (3, 5, 8, 8, 128, 2)
_SIX_HEADS = (2, 4, 6, 16, 128, 2)          # no whole block of 8 heads
_PUBLISHED = (2, 3, 32, 128, 256, 2)        # Falcon-H1-34B's head shapes
_NEMOTRON = (2, 3, 64, 64, 128, 8)          # four groups a block of 32 heads
_GRANITE = (2, 3, 128, 64, 128, 1)          # one group, four blocks
_ASKEW = (2, 3, 48, 64, 256, 4)     # blocks of 16 heads, groups of 12
_STACKS = {"tiny": _TINY, "six-heads": _SIX_HEADS, "published": _PUBLISHED,
           "nemotron-3-nano": _NEMOTRON, "granite-4.0-h-small": _GRANITE,
           "groups-askew-of-blocks": _ASKEW}


def draw_stack(seed, dims, dtype=jnp.float32, state_dtype=jnp.float32):
    layers, slots, heads, width, size, groups = dims
    ks = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(ks[0], (slots, heads, width)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (slots, heads)) - 2)
    a = -jnp.exp(jax.random.uniform(ks[2], (heads,), minval=0.0, maxval=2.5))
    b = jax.random.normal(ks[3], (slots, groups, size)).astype(dtype)
    c = jax.random.normal(ks[4], (slots, groups, size)).astype(dtype)
    states = jax.random.normal(
        ks[5], (layers, slots, heads, width, size)).astype(state_dtype)
    return x, dt, a, b, c, states


@pytest.mark.parametrize("operands", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("dims", _STACKS.values(), ids=_STACKS)
def test_state_kernel_is_the_plain_formulation(dims, operands):
    """``y`` and the new states of the active slots within float32
    rounding of the plain formulation's (the same products and sums, the
    reduction over the state's last axis in another order); an inactive
    slot's state and every other layer's bit for bit what they were."""
    x, dt, a, b, c, states = draw_stack(dims[1], dims, operands)
    slots = dims[1]
    active = jnp.arange(slots) != 1
    layer = jnp.int32(dims[0] - 2)
    want_y, want = ssm.ssm_state_step_reference(x, dt, a, b, c, states,
                                                layer, active)
    got_y, got = ssm.ssm_state_step_kernel(x, dt, a, b, c, states, layer,
                                           active, interpret=True)
    assert got_y.dtype == got.dtype == jnp.float32
    assert got_y.shape == want_y.shape and got.shape == states.shape
    live = np.asarray(active)
    np.testing.assert_allclose(np.asarray(got_y)[live],
                               np.asarray(want_y)[live], rtol=1e-5,
                               atol=1e-4)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    before, after = np.asarray(states), np.asarray(got)
    np.testing.assert_array_equal(after[layer, 1], before[layer, 1])
    others = [i for i in range(dims[0]) if i != int(layer)]
    np.testing.assert_array_equal(after[others], before[others])
    # and the active slots' did move
    assert not np.array_equal(after[layer, 0], before[layer, 0])


@pytest.mark.parametrize("dims", [_TINY, _PUBLISHED, _NEMOTRON, _GRANITE],
                         ids=["tiny", "published", "nemotron-3-nano",
                              "granite-4.0-h-small"])
def test_state_kernel_with_every_slot_inactive_moves_nothing(dims):
    x, dt, a, b, c, states = draw_stack(4, dims)
    _, got = ssm.ssm_state_step_kernel(
        x, dt, a, b, c, states, jnp.int32(1), jnp.zeros((dims[1],), bool),
        interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(states))


def test_state_kernel_steps_are_the_recurrence():
    """Four tokens through the kernel at one layer of the stack: the
    float64 recurrence's outputs and state, as ``ssm_step``'s are."""
    x, dt, a, b, c, s0 = draw(8, 3, 4)
    stack = jnp.zeros((2, *s0.shape)).at[1].set(s0)
    active = jnp.ones((3,), bool)
    for t in range(4):
        y, stack = ssm.ssm_state_step_kernel(
            x[:, t], dt[:, t], a, b[:, t], c[:, t], stack, jnp.int32(1),
            active, interpret=True)
        want_y, want_s = token_by_token(x[:, :t + 1], dt[:, :t + 1], a,
                                        b[:, :t + 1], c[:, :t + 1], s0)
        np.testing.assert_allclose(y, want_y[:, t], rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(stack[1], want_s, rtol=1e-5, atol=1e-5)
    assert not np.asarray(stack[0]).any()


_RULE = [
    ("published", (4, 128, 32, 128, 256), jnp.float32, True),
    ("tiny-lanes", (3, 5, 8, 8, 128), jnp.float32, True),
    ("bf16-state", (4, 128, 32, 128, 256), jnp.bfloat16, False),
    ("state-of-16", (2, 4, 6, 8, 16), jnp.float32, False),
    ("state-of-192", (2, 4, 8, 64, 192), jnp.float32, False),
    ("width-of-12", (2, 4, 8, 12, 128), jnp.float32, False),
    ("six-heads", (2, 4, 6, 16, 128), jnp.float32, True),
    ("twelve-large-heads", (2, 4, 12, 512, 256), jnp.float32, False),
    ("a-layers-slice", (128, 32, 128, 256), jnp.float32, False),
    ("the-tail", (4, 128, 3, 9248), jnp.bfloat16, False),
]


@pytest.mark.parametrize("shape,dtype,engages", [r[1:] for r in _RULE],
                         ids=[r[0] for r in _RULE])
def test_the_rule_reads_the_states_shape_and_dtype(shape, dtype, engages):
    """A float32 stack whose state size is whole lanes and whose head
    width is whole sublanes; anything else is the plain formulation's."""
    assert ssm.state_kernel_engages(
        jax.ShapeDtypeStruct(shape, dtype)) is engages


@pytest.mark.parametrize("dims,state_dtype", [
    (_TINY, jnp.bfloat16), ((2, 4, 6, 8, 16, 2), jnp.float32)],
    ids=["bf16-state", "state-of-16"])
def test_outside_the_rule_the_entry_is_the_plain_formulation(
        monkeypatch, dims, state_dtype):
    """The entry never reaches the kernel there (its launch is made to
    raise), lowers to the plain formulation's own text, and keeps the
    state's dtype."""
    def refuse(*args, **kwargs):
        raise AssertionError("the kernel was traced outside its rule")

    monkeypatch.setattr(ssm, "ssm_state_step_kernel", refuse)
    x, dt, a, b, c, states = draw_stack(2, dims, state_dtype=state_dtype)
    args = (x, dt, a, b, c, states, jnp.int32(1),
            jnp.arange(dims[1]) % 2 == 0)
    got_y, got = ssm.ssm_state_step(*args)
    want_y, want = ssm.ssm_state_step_reference(*args)
    assert got.dtype == state_dtype
    np.testing.assert_array_equal(np.asarray(got_y), np.asarray(want_y))
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    assert (jax.jit(ssm.ssm_state_step).lower(*args).as_text()
            == jax.jit(ssm.ssm_state_step_reference).lower(*args).as_text()
            .replace("ssm_state_step_reference", "ssm_state_step"))


def test_within_the_rule_the_cpu_runs_the_plain_formulation():
    """``platform_dependent``: a program lowered for the CPU holds the
    plain branch alone (no kernel call in its text) and gives the plain
    formulation's bits."""
    x, dt, a, b, c, states = draw_stack(3, _TINY)
    args = (x, dt, a, b, c, states, jnp.int32(2), jnp.arange(5) != 3)
    assert ssm.state_kernel_engages(states)
    got_y, got = jax.jit(ssm.ssm_state_step)(*args)
    want_y, want = jax.jit(ssm.ssm_state_step_reference)(*args)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got_y), np.asarray(want_y))
    text = jax.jit(ssm.ssm_state_step).lower(*args).compile().as_text()
    assert "tpu_custom_call" not in text
