"""Ulysses sequence-parallel attention + paged KV attention tests.
(both net-new vs the reference — SURVEY §2c SP rows; vLLM-style paging)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.attention import reference_attention
from ray_tpu.parallel.mesh import create_mesh
from ray_tpu.parallel.ulysses import ulysses_attention_sharded


@pytest.fixture(scope="module")
def sp_mesh():
    devices = jax.devices()
    assert len(devices) >= 4
    return create_mesh({"sp": 4}, devices=devices[:4])


def test_ulysses_matches_dense(sp_mesh):
    b, s, h, d = 2, 32, 8, 16
    key = jax.random.key(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, h, d), jnp.float32)
    k = jax.random.normal(kk, (b, s, h, d), jnp.float32)
    v = jax.random.normal(kv, (b, s, h, d), jnp.float32)

    expect = reference_attention(q, k, v, causal=True, scale=d ** -0.5)
    got = ulysses_attention_sharded(sp_mesh, q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               rtol=2e-4, atol=2e-4)


def test_ulysses_non_causal(sp_mesh):
    b, s, h, d = 1, 16, 4, 8
    key = jax.random.key(1)
    q = jax.random.normal(key, (b, s, h, d), jnp.float32)
    got = ulysses_attention_sharded(sp_mesh, q, q, q, causal=False)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, q) * (d ** -0.5)
    probs = jax.nn.softmax(logits, axis=-1)
    expect = jnp.einsum("bhqk,bkhd->bqhd", probs, q)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               rtol=2e-4, atol=2e-4)


def test_ulysses_head_divisibility_error(sp_mesh):
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel.ulysses import ulysses_attention

    q = jnp.zeros((1, 8, 6, 4))  # 6 heads not divisible by sp=4
    spec = P(None, "sp", None, None)
    fn = jax.shard_map(ulysses_attention, mesh=sp_mesh,
                   in_specs=(spec, spec, spec), out_specs=spec)
    with pytest.raises(ValueError, match="divisible"):
        fn(q, q, q)


# ---------------------------------------------------------------------------
# the paged-KV format (ops/paged_attention.py): write, read back, allocate
# ---------------------------------------------------------------------------

LAYERS, POOL, PAGE, NKV, GROUP, HEAD_DIM = 3, 12, 4, 2, 2, 8
LAYER = 1                   # written and read; the other layers stay zero
LENGTHS = [7, 10, 0]        # ragged; slot 2 is dead


def _ragged_slots(rng, dtype):
    """Pools, a page table of scattered pages (holes past each slot's own)
    and each slot's contiguous K and V rows [n, nkv, hd]."""
    shape = (LAYERS, POOL, PAGE, NKV, HEAD_DIM)
    pools = [jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)]
    scale_shape = shape[:-1] if dtype == jnp.int8 else (LAYERS, 1, 1, 1)
    pools += [jnp.ones(scale_shape, jnp.float32)] * 2
    table = np.full((len(LENGTHS), 4), -1, np.int32)
    free = list(rng.permutation(POOL))
    for slot, n in enumerate(LENGTHS):
        for i in range(-(-n // PAGE)):
            table[slot, i] = free.pop()
    rows = [(rng.normal(size=(n, NKV, HEAD_DIM)).astype(np.float32),
             rng.normal(size=(n, NKV, HEAD_DIM)).astype(np.float32))
            for n in LENGTHS]
    return pools, table, rows


def _write_rows(pools, table, rows, quantized):
    """Every slot's rows through ``write_kv`` at layer LAYER, as the
    engine's prefill does: [n, T] indices, padding and dead slots named
    by the index one past the pool."""
    from ray_tpu.ops.paged_attention import write_kv

    t = max(LENGTHS)
    k_new = np.zeros((len(LENGTHS), t, NKV, HEAD_DIM), np.float32)
    v_new = np.zeros_like(k_new)
    pidx = np.full((len(LENGTHS), t), POOL, np.int32)
    for slot, (k, v) in enumerate(rows):
        n = len(k)
        k_new[slot, :n], v_new[slot, :n] = k, v
        pidx[slot, :n] = table[slot, np.arange(n) // PAGE]
    ip = np.broadcast_to(np.arange(t) % PAGE, pidx.shape)
    return write_kv(*pools, jnp.int32(LAYER), jnp.asarray(k_new),
                    jnp.asarray(v_new), jnp.asarray(pidx), jnp.asarray(ip),
                    quantized)


def test_rows_written_to_scattered_pages_read_back_as_plain_attention():
    """Ragged slots over scattered pages, grouped heads: rows written at
    [layer, page, offset] and read back through the page table, by
    ``gather_kv_window`` + ``cached_attention`` (the engine's prefill)
    and by the decode attention's gather formulation, give plain
    attention over each slot's contiguous rows."""
    from ray_tpu.ops.attention import cached_attention
    from ray_tpu.ops.paged_attention import gather_kv_window
    from ray_tpu.ops.paged_decode_attention import \
        paged_decode_attention_reference

    rng = np.random.default_rng(0)
    pools, table, rows = _ragged_slots(rng, jnp.float32)
    pools = _write_rows(pools, table, rows, quantized=False)
    for other in (0, 2):        # that layer's pages only
        assert not np.asarray(pools[0][other]).any()
    b, nh = len(LENGTHS), NKV * GROUP
    q = rng.normal(size=(b, nh, HEAD_DIM)).astype(np.float32)
    pos = np.maximum(np.array(LENGTHS) - 1, 0).astype(np.int32)
    live = np.array(LENGTHS) > 0
    args = (jnp.int32(LAYER), jnp.asarray(table))
    kg, vg = gather_kv_window(*pools, *args)
    got = cached_attention(
        jnp.asarray(q)[:, None], kg.reshape(b, -1, NKV, HEAD_DIM),
        vg.reshape(b, -1, NKV, HEAD_DIM), jnp.asarray(pos),
        scale=HEAD_DIM ** -0.5)[:, 0]
    ref = paged_decode_attention_reference(
        jnp.asarray(q), *pools, *args, jnp.asarray(pos), jnp.asarray(live))
    for slot, (k, v) in enumerate(rows):
        if not live[slot]:
            continue
        k_r = np.repeat(k, GROUP, axis=1)           # [n, nh, hd]
        v_r = np.repeat(v, GROUP, axis=1)
        logits = np.einsum("hd,khd->hk", q[slot], k_r) * HEAD_DIM ** -0.5
        probs = np.exp(logits - logits.max(-1, keepdims=True))
        probs /= probs.sum(-1, keepdims=True)
        want = np.einsum("hk,khd->hd", probs, v_r)
        for out in (got, ref):
            np.testing.assert_allclose(np.asarray(out[slot]), want,
                                       rtol=2e-4, atol=2e-4)


def test_int8_write_keeps_the_quantisers_error_and_drops_outside_the_pool():
    """The same write through int8 pages and their scale pools: what
    comes back is the row to within the quantiser's step, and an index
    outside the pool (a dead slot, padding, a hole) writes nothing."""
    from ray_tpu.ops.paged_attention import dequantize_kv

    rng = np.random.default_rng(1)
    pools, table, rows = _ragged_slots(rng, jnp.int8)
    kp, vp, ks, vs = _write_rows(pools, table, rows, quantized=True)
    assert kp.dtype == jnp.int8 and ks.shape == kp.shape[:-1]
    k_back = np.asarray(dequantize_kv(kp, ks, jnp.float32))[LAYER]
    v_back = np.asarray(dequantize_kv(vp, vs, jnp.float32))[LAYER]
    written = np.zeros((POOL, PAGE), bool)
    for slot, (k, v) in enumerate(rows):
        for i in range(len(k)):
            at = (table[slot, i // PAGE], i % PAGE)
            written[at] = True
            for back, row in ((k_back, k), (v_back, v)):
                # half a step of a symmetric 127-level grid a (token, head)
                step = np.abs(row[i]).max(axis=-1, keepdims=True) / 127.0
                assert (np.abs(back[at] - row[i]) <= 0.51 * step).all()
    assert written.sum() == sum(LENGTHS)
    # everything else is as it was: zero pages, unit scales
    assert not np.asarray(kp)[LAYER][~written].any()
    assert (np.asarray(ks)[LAYER][~written] == 1.0).all()
    for other in (0, 2):
        assert not np.asarray(kp)[other].any()
        assert (np.asarray(ks)[other] == 1.0).all()


def test_page_allocator_reuse_and_exhaustion():
    from ray_tpu.ops.paged_attention import PageAllocator

    alloc = PageAllocator(4)
    a = alloc.alloc(0, 3)
    assert len(set(a)) == 3
    with pytest.raises(MemoryError):
        alloc.alloc(1, 2)
    alloc.free_slot(0)
    b = alloc.alloc(1, 4)
    assert len(set(b)) == 4


def test_free_slot_returns_that_slots_pages_only():
    """Two slots hold the whole pool: a request past it raises and takes
    nothing; freeing one slot returns exactly its pages, and they are
    what the next request gets."""
    from ray_tpu.ops.paged_attention import PageAllocator

    alloc = PageAllocator(8)
    mine, theirs = alloc.alloc(0, 5), alloc.alloc(1, 3)
    assert not alloc.free and not set(mine) & set(theirs)
    with pytest.raises(MemoryError):
        alloc.alloc(2, 1)
    assert 2 not in alloc.owned and alloc.owned[1] == theirs
    alloc.free_slot(0)
    assert sorted(alloc.free) == sorted(mine) and 0 not in alloc.owned
    assert alloc.owned[1] == theirs
    alloc.free_slot(0)                       # nothing left to return
    assert len(alloc.free) == 5
    assert sorted(alloc.alloc(2, 5)) == sorted(mine)


def test_ops_import_nothing_from_the_layers_above():
    """``ray_tpu/ops`` is the bottom layer: no module of it imports from
    ``ray_tpu.models``, ``ray_tpu.serve`` or ``ray_tpu.train``, at the
    top of the file or inside a function."""
    import ast
    import pathlib

    import ray_tpu.ops

    above = ("ray_tpu.models", "ray_tpu.serve", "ray_tpu.train")
    found = []
    for path in sorted(pathlib.Path(ray_tpu.ops.__file__).parent.rglob("*.py")):
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom) and not node.level:
                names = [f"{node.module}.{a.name}" for a in node.names]
            else:
                continue
            found += [(path.name, n) for n in names
                      if any(n == a or n.startswith(a + ".") for a in above)]
    assert not found
