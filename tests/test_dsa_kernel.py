"""``veles_dsa_prefill`` (``ops/pallas/dsa.py``), a prefill pass's masked
attention, against the XLA loop it replaces
(``ops.attention.dsa_attend_blocks``) — in interpret mode, at small
shapes the kernel's predicate takes: head dim 128, key blocks of 128.

The kernel gets the mask the selection would give it, block-major and
int8; what lies past the live blocks — K, V and mask alike — is poison
that must not move a bit of the output."""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from veles_tpu.ops import attention
from veles_tpu.ops.pallas import dsa

HD, KB, TK = 128, 128, 512
SCALE = HD ** -0.5


@pytest.fixture
def key_blocks_of_128(monkeypatch):
    monkeypatch.setattr(attention, "DSA_KEY_BLOCK", KB)


def _operands(g, tq, q_start, seed, dtype=jnp.float32, hkv=2, keep=0.3):
    """q, k, v and a causal random mask in which every query selects
    itself, block-major."""
    rng = np.random.default_rng(seed)

    def a(*shape):
        return jnp.asarray(rng.normal(size=shape), dtype)

    qpos = q_start + np.arange(tq)
    kpos = np.arange(TK)
    mask = (rng.uniform(size=(1, tq, TK)) < keep) \
        & (kpos[None, None] <= qpos[None, :, None])
    mask[0, np.arange(tq), qpos] = True
    return (a(1, hkv, g, tq, HD), a(1, hkv, TK, HD), a(1, hkv, TK, HD),
            _block_major(mask))


def _block_major(mask):
    b, tq, tk = mask.shape
    return jnp.asarray(mask.reshape(b, tq, tk // KB, KB)
                       .transpose(2, 0, 1, 3), jnp.int8)


def _poison(k, v, mask, n_live):
    """the same operands with everything past the live blocks ruined"""
    k, v = (x.at[:, :, n_live * KB:].set(jnp.nan) for x in (k, v))
    return k, v, mask.at[n_live:].set(1)


#: (first query's position, as a share of what the row leaves free)
POSITIONS = {"row_start": 0.0, "mid_row": 0.5, "row_end": 1.0}


@pytest.mark.parametrize("where", sorted(POSITIONS))
@pytest.mark.parametrize("tq", [32, 96], ids=["one_tile", "three_tiles"])
@pytest.mark.parametrize("g", [1, 2, 8])
def test_kernel_is_the_loop_over_the_live_blocks(g, tq, where):
    """Every group size, one q tile and several, a pass at the start of
    a row (one live block), in its middle and at its end (all of them),
    ``n_live`` traced: the loop's output to float32 rounding, and not a
    bit of it moved by what lies past the live blocks."""
    q_start = int((TK - tq) * POSITIONS[where]) // 8 * 8
    n_live = -(-(q_start + tq) // KB)
    assert n_live == {"row_start": 1, "mid_row": 3,
                      "row_end": TK // KB}[where]
    q, k, v, mask = _operands(g, tq, q_start, seed=g * tq + q_start)
    want = attention.dsa_attend_blocks(q, k, v, mask, n_live, SCALE)

    @jax.jit
    def kernel(k, v, mask, q_start, n_live):
        return dsa.dsa_prefill_attention(q, k, v, mask, q_start, n_live,
                                         SCALE, tiles=(32, KB))

    got = kernel(k, v, mask, jnp.int32(q_start), jnp.int32(n_live))
    assert got.shape == q.shape and got.dtype == q.dtype
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    ruined = kernel(*_poison(k, v, mask, n_live), jnp.int32(q_start),
                    jnp.int32(n_live))
    assert np.isfinite(np.asarray(ruined)).all()
    np.testing.assert_array_equal(got, ruined)


@pytest.mark.parametrize("n_live", [1, 2, 4])
def test_static_live_blocks_and_the_shapes_own_tiles(n_live):
    """``n_live`` and ``q_start`` as Python ints (the training forward's
    static trip counts), at the tiles ``prefill_tiles`` gives the shapes
    (one q tile of 64; key tiles of a whole block)."""
    tq = 64
    q_start = n_live * KB - tq
    q, k, v, mask = _operands(2, tq, q_start, seed=n_live)
    assert dsa.prefill_tiles(tq, TK, HD, KB, 2, 4) == (64, KB)
    want = attention.dsa_attend_blocks(q, k, v, mask, n_live, SCALE)
    got = dsa.dsa_prefill_attention(q, *_poison(k, v, mask, n_live),
                                    q_start, n_live, SCALE)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


def test_a_mask_tile_that_is_all_false_contributes_nothing():
    """A whole (q tile, key block) of the mask False — in the middle of
    the row, with selected keys on both sides: its K and V (huge here)
    never reach the output, before or after the row's maximum is
    finite."""
    tq, q_start, n_live = 64, TK - 64, TK // KB
    q, k, v, mask = _operands(8, tq, q_start, seed=5, hkv=1)
    mask = mask.at[1, :, :32].set(0)      # block 1 of q tile 0
    mask = mask.at[0, :, 32:].set(0)      # block 0 of q tile 1
    k = k.at[:, :, KB:2 * KB].multiply(50.0)
    want = attention.dsa_attend_blocks(q, k, v, mask, n_live, SCALE)
    got = dsa.dsa_prefill_attention(q, k, v, mask, q_start, n_live, SCALE,
                                    tiles=(32, KB))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    # the tile's values cannot matter: ruin them
    ruined = dsa.dsa_prefill_attention(
        q, k, v.at[:, :, :KB].set(1e30), mask.at[0, :, :32].set(0),
        q_start, n_live, SCALE, tiles=(32, KB))
    assert np.isfinite(np.asarray(ruined[:, :, :, :32])).all()


def _indexed(tq, tk, dtype, seed=3, h=4, hkv=2):
    rng = np.random.default_rng(seed)

    def a(*shape):
        return jnp.asarray(rng.normal(size=shape), dtype)

    return dict(q=a(1, h, tq, HD), k=a(1, hkv, tk, HD), v=a(1, hkv, tk, HD),
                qi=a(1, 2, tq, 16), ki=a(1, 1, tk, 16),
                wi=jnp.asarray(rng.normal(size=(1, tq, 2)), jnp.float32))


@contextlib.contextmanager
def _path(kernel, monkeypatch):
    """The kernel's path or, the predicate switched off, the loop's;
    yields the list the kernel's calls are counted in, at trace."""
    calls = []
    real = dsa.dsa_prefill_attention
    with monkeypatch.context() as m:
        m.setattr(dsa, "dsa_prefill_attention",
                  lambda *a, **kw: calls.append(1) or real(*a, **kw))
        if not kernel:
            m.setattr(attention, "dsa_prefill_tiles", lambda *a: None)
        yield calls


def _attend(t, q_start, topk, live_keys, kernel, monkeypatch):
    """``dsa_attend`` down either path, and the kernel's calls."""
    with _path(kernel, monkeypatch) as calls:
        out = jax.jit(lambda q, k, v, start, live: attention.dsa_attend(
            q, k, v, t["qi"], t["ki"], t["wi"], start, topk,
            live_keys=live))(t["q"], t["k"], t["v"], q_start, live_keys)
    return out, len(calls)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_a_pass_whose_early_queries_select_fewer_than_topk(
        dtype, key_blocks_of_128, monkeypatch):
    """The whole ``dsa_attend`` at the start of a row: queries 0..46 have
    fewer than ``topk`` = 48 keys to select from, the rest exactly 48 —
    the kernel's path against the loop's, same selection, same output
    (the operands' own dtype through the matmuls on both)."""
    t = _indexed(64, TK, dtype)
    got, calls = _attend(t, jnp.int32(0), 48, jnp.int32(64), True,
                         monkeypatch)
    want, none = _attend(t, jnp.int32(0), 48, jnp.int32(64), False,
                         monkeypatch)
    assert (calls, none) == (1, 0)
    assert got.dtype == dtype and np.isfinite(
        np.asarray(got, np.float32)).all()
    tol = 2e-5 if dtype == jnp.float32 else 1e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_a_row_the_key_block_does_not_divide_takes_the_loop(
        key_blocks_of_128, monkeypatch):
    """``tk`` = 320 = 2.5 blocks: the predicate refuses, no kernel is
    traced, and the loop (whose last block overlaps its neighbour) gives
    the masked softmax attention over the selected keys."""
    tk, topk = 320, 40
    assert attention.dsa_prefill_tiles(64, tk, HD) is None
    assert attention.dsa_prefill_tiles(64, 384, HD) == (64, KB)
    t = _indexed(64, tk, jnp.float32)
    got, calls = _attend(t, jnp.int32(tk - 64), topk, jnp.int32(tk), True,
                         monkeypatch)
    assert calls == 0
    qpos = tk - 64 + np.arange(64)
    valid = jnp.asarray(np.arange(tk)[None] <= qpos[:, None])[None]
    chosen = attention.dsa_select(
        attention.index_scores(t["qi"], t["ki"][:, 0], t["wi"]), valid,
        topk)
    kr, vr = (jnp.repeat(a, 2, axis=1) for a in (t["k"], t["v"]))
    s = jnp.einsum("bhqd,bhkd->bhqk", t["q"], kr) * SCALE
    p = jax.nn.softmax(jnp.where(chosen[:, None], s, -jnp.inf), -1)
    np.testing.assert_allclose(got, jnp.einsum("bhqk,bhkd->bhqd", p, vr),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("shape,why", [
    ((2048, 34816, 64), "head dim under the lanes"),
    ((2048, 34816 + 512, 128), "a row the key block does not divide"),
    ((40, 34816, 128), "queries that do not fill the mask's tile"),
    ((64, 192, 128), "a key block that is not lane-wide"),
])
def test_shapes_the_predicate_refuses(shape, why):
    assert attention.dsa_prefill_tiles(*shape) is None, why


@pytest.mark.parametrize("tq", [256, 512, 1024, 2048])
def test_the_cells_passes_tile_inside_the_vmem_budget(tq):
    """Every pass length of ``keye30.serve_long`` (256 to 2,048 tokens
    over a row of 34,816) runs in the kernel, at tiles whose VMEM
    (``_vmem_bytes``) is inside the stated budget."""
    tq_tile, kt = attention.dsa_prefill_tiles(tq, 34816, 128)
    assert tq % tq_tile == 0 and 1024 % kt == 0
    assert dsa._vmem_bytes(8, tq_tile, kt, 128, 2) <= dsa._VMEM_BUDGET
    # a group too large for the largest tiles halves them, keys first
    assert dsa.prefill_tiles(tq, 34816, 128, 1024, g=32) != (tq_tile, kt)


def test_the_gradient_through_the_kernel_is_the_loops(key_blocks_of_128,
                                                      monkeypatch):
    """``dsa_attend`` stays differentiable where the forward runs in the
    kernel (``live_keys=None``: the training forward): the cotangents of
    q, k and v are the loop's for the same mask."""
    t = _indexed(64, 256, jnp.float32, seed=9)

    def loss(q, k, v):
        return jnp.sum(jnp.sin(attention.dsa_attend(
            q, k, v, t["qi"], t["ki"], t["wi"], 0, 24)))

    def grads(kernel):
        with _path(kernel, monkeypatch) as calls:
            out = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))(
                t["q"], t["k"], t["v"])
        return out, len(calls)

    (got, got_grads), calls = grads(True)
    (want, want_grads), none = grads(False)
    assert (calls, none) == (1, 0)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for a, b in zip(got_grads, want_grads):
        assert float(jnp.max(jnp.abs(b))) > 1e-3
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)
