"""Attention stack: naive vs blockwise vs Pallas flash, and the two
sequence-parallel strategies (ring, Ulysses) on the virtual 8-device mesh.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from veles_tpu.ops import attention as att
from veles_tpu.parallel import ring
from veles_tpu.parallel.mesh import make_mesh


def _qkv(b=2, h=4, t=64, d=16, seed=0, dtype=np.float32):
    r = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(r.randn(b, h, t, d).astype(dtype))
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("t", [64, 57])       # 57: exercises padding
def test_blockwise_matches_naive(causal, t):
    q, k, v = _qkv(t=t)
    ref = att.attention(q, k, v, causal=causal)
    out = att.blockwise_attention(q, k, v, causal=causal, block_k=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_pallas_matches_naive(causal):
    q, k, v = _qkv(t=128, d=32)
    ref = att.attention(q, k, v, causal=causal)
    out = att.flash_attention(q, k, v, causal=causal, block_q=32,
                              block_k=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_pallas_differentiable(causal):
    """flash impl must be trainable: grads match the naive reference."""
    q, k, v = _qkv(t=32, d=16)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v, causal=causal) ** 2)

    g_ref = jax.grad(loss(att.attention), argnums=(0, 1, 2))(q, k, v)
    g_flash = jax.grad(loss(lambda *a, **kw: att.flash_attention(
        *a, block_q=16, block_k=16, **kw)), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_flash_pallas_padding():
    q, k, v = _qkv(t=100, d=16)
    ref = att.attention(q, k, v)
    out = att.flash_attention(q, k, v, block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal,t", [(False, 64), (True, 64),
                                      (False, 57), (True, 57)])
def test_flash_fused_backward_matches_naive(causal, t):
    """The Pallas dQ / dK-dV kernels (backward='fused', the default) must
    reproduce the naive reference gradients — incl. ragged T padding."""
    q, k, v = _qkv(t=t, d=16, seed=3)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) ** 3)

    g_ref = jax.grad(loss(lambda q, k, v: att.attention(
        q, k, v, causal=causal)), argnums=(0, 1, 2))(q, k, v)
    g_fused = jax.grad(loss(lambda q, k, v: att.flash_attention(
        q, k, v, causal=causal, block_q=16, block_k=16,
        backward="fused")), argnums=(0, 1, 2))(q, k, v)
    g_rec = jax.grad(loss(lambda q, k, v: att.flash_attention(
        q, k, v, causal=causal, block_q=16, block_k=16,
        backward="recompute")), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g_fused, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4,
                                   err_msg="d%s (vs naive)" % name)
    for name, a, b in zip("qkv", g_fused, g_rec):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4,
                                   err_msg="d%s (vs recompute)" % name)


def test_flash_fused_backward_cross_attention():
    """tq != tk (non-causal cross attention) through the fused backward."""
    r = np.random.RandomState(9)
    q = jnp.asarray(r.randn(2, 2, 48, 16).astype(np.float32))
    k = jnp.asarray(r.randn(2, 2, 80, 16).astype(np.float32))
    v = jnp.asarray(r.randn(2, 2, 80, 16).astype(np.float32))

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

    g_ref = jax.grad(loss(att.attention), argnums=(0, 1, 2))(q, k, v)
    g_fused = jax.grad(loss(lambda *a: att.flash_attention(
        *a, block_q=16, block_k=32)), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_fused, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_flash_fused_backward_bf16():
    """bf16 storage dtype: fused grads stay within bf16 tolerance of the
    f32 naive reference and carry the input dtype."""
    q, k, v = _qkv(t=64, d=16, seed=4)
    q16, k16, v16 = (x.astype(jnp.bfloat16) for x in (q, k, v))

    g_ref = jax.grad(lambda q, k, v: jnp.sum(
        att.attention(q, k, v, causal=True) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    g16 = jax.grad(lambda q, k, v: jnp.sum(
        att.flash_attention(q, k, v, causal=True, block_q=16,
                            block_k=16).astype(jnp.float32) ** 2),
        argnums=(0, 1, 2))(q16, k16, v16)
    for a, b in zip(g16, g_ref):
        assert a.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b), rtol=0.1, atol=0.15)


@pytest.mark.parametrize("axes,spec", [
    ({"data": 4}, ("data",)),
    ({"data": 2, "model": 2}, ("data", "model")),
    ({"data": 4, "model": 2}, ("data",)),      # 3 heads: model unsplit
])
def test_flash_under_a_data_model_mesh_runs_per_device(axes, spec):
    """The shape the chip refused: on silicon a bare pallas_call inside
    a partitioned program does not lower at all ("Mosaic kernels cannot
    be automatically partitioned").  ``shard=`` runs the kernel per
    device under shard_map — batch over data, heads over model — and
    must change nothing: outputs, gradients, and an axis that does not
    divide its dim is left unsplit, never an error."""
    from jax.sharding import PartitionSpec as P
    mesh = make_mesh(axes)
    h = 4 if spec == ("data", "model") or "model" not in axes else 3
    q, k, v = _qkv(b=4, h=h, t=64, d=16)
    shard = (mesh, "data", "model")

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

    plain = lambda q, k, v: att.flash_attention(q, k, v, causal=True)  # noqa
    sharded = lambda q, k, v: att.flash_attention(  # noqa: E731
        q, k, v, causal=True, shard=shard)
    out = jax.jit(sharded)(q, k, v)
    assert out.sharding.spec == P(*spec)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(plain(q, k, v)))
    got = jax.jit(jax.grad(loss(sharded), argnums=(0, 1, 2)))(q, k, v)
    want = jax.grad(loss(plain), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)
    # a batch the data axis does not divide: no split, same answer
    odd = jax.jit(sharded)(q[:3], k[:3], v[:3])
    np.testing.assert_array_equal(np.asarray(odd),
                                  np.asarray(plain(q[:3], k[:3], v[:3])))


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention(causal):
    mesh = make_mesh({"seq": 8})
    q, k, v = _qkv(b=1, h=2, t=64, d=8)
    ref = att.attention(q, k, v, causal=causal)
    out = ring.ring_attention_sharded(q, k, v, mesh, causal=causal,
                                      block_k=8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.slow
def test_ring_attention_grad():
    mesh = make_mesh({"seq": 4})
    q, k, v = _qkv(b=1, h=2, t=32, d=8)

    def loss_ring(q):
        return jnp.sum(ring.ring_attention_sharded(
            q, k, v, mesh, causal=True, block_k=8) ** 2)

    def loss_ref(q):
        return jnp.sum(att.attention(q, k, v, causal=True) ** 2)

    g_ring = jax.grad(loss_ring)(q)
    g_ref = jax.grad(loss_ref)(q)
    np.testing.assert_allclose(np.asarray(g_ring), np.asarray(g_ref),
                               rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention(causal):
    mesh = make_mesh({"seq": 4})
    q, k, v = _qkv(b=1, h=8, t=64, d=8)
    ref = att.attention(q, k, v, causal=causal)
    out = ring.ulysses_attention_sharded(q, k, v, mesh, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_mha_forward_and_grad():
    from veles_tpu import prng
    prng.seed_all(7)
    rng = prng.get("mha-test")
    d_model, n_heads = 32, 4
    params = att.mha_init(rng, d_model, n_heads)
    x = jnp.asarray(np.random.RandomState(1).randn(2, 16, d_model)
                    .astype(np.float32))
    y = att.mha_forward(params, x, n_heads, causal=True)
    assert y.shape == x.shape
    y_naive = att.mha_forward(params, x, n_heads, causal=True, impl="naive")
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_naive),
                               rtol=2e-5, atol=2e-5)
    g = jax.grad(lambda p: jnp.sum(
        att.mha_forward(p, x, n_heads, causal=True) ** 2))(params)
    assert jnp.all(jnp.isfinite(g["wq"]))


def test_flash_bf16_inputs_match_f32_reference():
    """Mixed precision: bf16 q/k/v multiply on the MXU at native rate
    while softmax stats and the output accumulator stay f32 — results
    must track the f32 reference within bf16 tolerance."""
    import jax
    import jax.numpy as jnp

    from veles_tpu.ops.attention import attention
    from veles_tpu.ops.pallas.flash import flash_attention

    key = jax.random.key(4)
    q, k, v = (jax.random.normal(kk, (2, 2, 256, 64), jnp.float32) * 0.3
               for kk in jax.random.split(key, 3))
    out = flash_attention(q.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
                          v.astype(jnp.bfloat16), causal=True)
    ref = attention(q, k, v, causal=True)
    assert out.dtype == jnp.bfloat16
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref))) < 0.05


def test_flash_mixed_dtypes_rejected():
    import jax
    import jax.numpy as jnp
    import pytest

    from veles_tpu.ops.pallas.flash import flash_attention
    key = jax.random.key(1)
    q, k, v = (jax.random.normal(kk, (1, 1, 64, 32), jnp.float32)
               for kk in jax.random.split(key, 3))
    with pytest.raises(ValueError, match="matching q/k/v dtypes"):
        flash_attention(q, k.astype(jnp.bfloat16), v)


@pytest.mark.parametrize("impl", ["ring", "ulysses"])
def test_rope_with_sequence_parallel_mha(impl, f32_precision):
    """RoPE rotates the GLOBAL q/k before the seq-parallel shard_map, so
    ring/Ulysses attention under rope must match the single-device path."""
    from veles_tpu.models.layers import make_layer
    from veles_tpu import prng

    # seq=4: ulysses also needs n_heads (4) divisible by the axis size
    mesh = make_mesh({"seq": 4}, jax.devices()[:4])
    r = np.random.RandomState(11)
    x = jnp.asarray(r.randn(2, 16, 32).astype(np.float32))

    def out_for(impl_name, with_mesh):
        prng.seed_all(13)
        layer = make_layer({"type": "multihead_attention", "n_heads": 4,
                            "causal": True, "rope": True,
                            "impl": impl_name})
        layer.setup((16, 32))
        if with_mesh:
            layer.mesh = mesh
        params = layer.init_params(prng.get("w"))
        return np.asarray(layer.apply(params, x))

    got = out_for(impl, True)
    want = out_for("blockwise", False)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("t,w", [(64, 16), (64, 3), (57, 16),
                                 # shrunken-grid edges: window spanning
                                 # several blocks, window > t (span
                                 # clamps to nk), window == block, and
                                 # a window that overshoots past the
                                 # last k block on tail q blocks
                                 (128, 40), (64, 100), (64, 32),
                                 (96, 33)])
def test_flash_sliding_window(t, w):
    """Sliding-window causal flash: forward AND fused backward must
    match the masked naive reference (incl. ragged padding)."""
    q, k, v = _qkv(t=t, d=16, seed=6)

    ref = att.attention(q, k, v, causal=True, window=w)
    out = att.flash_attention(q, k, v, causal=True, window=w,
                              block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

    g_ref = jax.grad(loss(lambda q, k, v: att.attention(
        q, k, v, causal=True, window=w)), argnums=(0, 1, 2))(q, k, v)
    g_flash = jax.grad(loss(lambda q, k, v: att.flash_attention(
        q, k, v, causal=True, window=w, block_q=16, block_k=16)),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_flash_window_validation():
    q, k, v = _qkv(t=32, d=16)
    with pytest.raises(ValueError, match="causal"):
        att.flash_attention(q, k, v, window=8)
    with pytest.raises(ValueError, match=">= 1"):
        att.flash_attention(q, k, v, causal=True, window=0)


def test_flash_block_sizes_from_site_config():
    """Site config sets the kernel's default tile sizes — a flashtune
    winner bakes in with no code edit.  d <= 64 resolves the *_d64
    keys (that regime fits — and wants — bigger blocks, measured
    2026-08-01); d > 64 resolves block_q/block_k as before."""
    from veles_tpu.config import root

    from veles_tpu.ops.pallas import flash as flash_mod

    q, k, v = _qkv(t=64, d=16)
    ref = att.attention(q, k, v, causal=True)
    root.common.engine.flash.block_q_d64 = 32
    root.common.engine.flash.block_k_d64 = 16
    # the d>64 keys must NOT leak into the small-d resolution
    root.common.engine.flash.block_q = 64
    root.common.engine.flash.block_k = 64
    flash_mod._flash_fn.cache_clear()
    try:
        out = att.flash_attention(q, k, v, causal=True, interpret=True)
        # the kernel really resolved the CONFIG sizes (the lru_cache
        # key holds the resolved block_q/block_k), via the public
        # wrapper
        assert flash_mod._flash_fn.cache_info().currsize == 1
        out2 = flash_mod.flash_attention(q, k, v, causal=True,
                                         block_q=32, block_k=16,
                                         interpret=True)
        # same (causal, scale, 32, 16, ...) signature -> cache HIT
        assert flash_mod._flash_fn.cache_info().currsize == 1
        np.testing.assert_allclose(np.asarray(out2), np.asarray(out),
                                   rtol=0, atol=0)
    finally:
        del root.common.engine.flash.block_q_d64
        del root.common.engine.flash.block_k_d64
        del root.common.engine.flash.block_q
        del root.common.engine.flash.block_k
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_small_d_defaults_cap_at_padded_t():
    """Unset *_d64 keys: the small-d default caps at min(1024,
    padded T), so a T=64 call resolves 128-sized blocks — the lru
    cache key it lands on must be the same one an explicit (128, 128)
    call hits, and the output matches the reference."""
    from veles_tpu.ops.pallas import flash as flash_mod

    q, k, v = _qkv(t=64, d=16)
    ref = att.attention(q, k, v, causal=True)
    flash_mod._flash_fn.cache_clear()
    out = att.flash_attention(q, k, v, causal=True, interpret=True)
    assert flash_mod._flash_fn.cache_info().currsize == 1
    flash_mod.flash_attention(q, k, v, causal=True, block_q=128,
                              block_k=128, interpret=True)
    assert flash_mod._flash_fn.cache_info().currsize == 1  # cache HIT
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_blockwise_window_validation():
    """blockwise_attention is a public entry point (the ring carry API) —
    window without causal must raise, not silently run full attention."""
    q, k, v = _qkv(t=32, d=16)
    with pytest.raises(ValueError, match="causal"):
        att.blockwise_attention(q, k, v, causal=False, window=8)


def test_mha_window_validated_for_all_impls():
    """window misconfigs must raise identically on every impl path."""
    from veles_tpu import prng
    prng.seed_all(3)
    params = att.mha_init(prng.get("w"), 16, 2)
    x = jnp.zeros((1, 8, 16), jnp.float32)
    for impl in ("blockwise", "naive", "flash"):
        with pytest.raises(ValueError, match="causal"):
            att.mha_forward(params, x, 2, causal=False, impl=impl,
                            window=4)
        with pytest.raises(ValueError, match=">= 1"):
            att.mha_forward(params, x, 2, causal=True, impl=impl,
                            window=0)


# --------------------------------------------------------------------------
# Paged-KV decode kernel (ops/pallas/paged.py)
# --------------------------------------------------------------------------

def _paged_case(pos, hkv=2, g=1, bs=16, nbm=8, hd=128, dtype=jnp.float32,
                free=(), seed=0):
    """One row a ``pos`` entry, each owning the pool pages of its live
    prefix (DISTINCT ids, shuffled — the batcher's allocation
    invariant; dead entries stay 0, the dummy); rows listed in ``free``
    are free slots (table all 0).  Returns the kernel's arguments."""
    r = np.random.RandomState(seed)
    pos = np.asarray(pos, np.int32)
    b = len(pos)
    q = jnp.asarray(r.randn(b, hkv * g, hd), dtype)
    pk = jnp.asarray(r.randn(1 + b * nbm, hkv, bs, hd), dtype)
    pv = jnp.asarray(r.randn(1 + b * nbm, hkv, bs, hd), dtype)
    ids = r.permutation(b * nbm).reshape(b, nbm) + 1
    table = np.zeros((b, nbm), np.int32)
    for i in range(b):
        if i not in free:
            live = pos[i] // bs + 1
            table[i, :live] = ids[i, :live]
    return q, pk, pv, jnp.asarray(table), jnp.asarray(pos)


def _paged_setup(b=3, hkv=2, g=4, bs=16, nbm=4, hd=64,
                 dtype=jnp.float32, seed=0):
    """Three rows of staggered lengths: the first key only, the middle
    of the table, the last key of a full table."""
    pos = [0, (nbm // 2) * bs + 3, nbm * bs - 1][:b]
    return _paged_case(pos, hkv=hkv, g=g, bs=bs, nbm=nbm, hd=hd,
                       dtype=dtype, seed=seed)


@pytest.mark.parametrize("g,dtype,tol", [
    (1, jnp.float32, 2e-6), (4, jnp.float32, 2e-6),
    (4, jnp.bfloat16, 2e-2)])
def test_paged_decode_matches_reference(g, dtype, tol):
    from veles_tpu.ops.pallas.paged import (paged_attention_decode,
                                            paged_attention_reference)
    q, pk, pv, table, pos = _paged_setup(g=g, dtype=dtype)
    ref = paged_attention_reference(q, pk, pv, table, pos)
    out = paged_attention_decode(q, pk, pv, table, pos, interpret=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_paged_decode_reference_matches_dense_softmax():
    """The reference formulation itself against a hand-built dense
    masked softmax — pins the exact semantics (live = pos inclusive)."""
    from veles_tpu.ops.pallas.paged import paged_attention_reference
    q, pk, pv, table, pos = _paged_setup(b=2, g=1, bs=4, nbm=3, hd=8)
    b, hq, hd = q.shape
    out = np.asarray(paged_attention_reference(q, pk, pv, table, pos))
    for i in range(b):
        n = int(pos[i]) + 1
        ks, vs = [], []
        for t in range(n):
            blk, off = int(table[i, t // 4]), t % 4
            ks.append(np.asarray(pk)[blk, :, off])
            vs.append(np.asarray(pv)[blk, :, off])
        k = np.stack(ks, 1)                       # [hkv, n, hd]
        v = np.stack(vs, 1)
        s = np.einsum("hd,htd->ht", np.asarray(q)[i], k) * hd ** -0.5
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        want = np.einsum("ht,htd->hd", p, v)
        np.testing.assert_allclose(out[i], want, rtol=2e-5, atol=2e-5)


def test_paged_decode_dead_blocks_cannot_leak():
    """Garbage in the dummy block and in allocated-but-beyond-pos
    blocks must not change the output (masking, not data layout, is
    what keeps dead keys out)."""
    from veles_tpu.ops.pallas.paged import paged_attention_decode
    q, pk, pv, table, pos = _paged_setup()
    base = np.asarray(paged_attention_decode(q, pk, pv, table, pos,
                                             interpret=True), np.float32)
    poison = jnp.full(pk.shape[1:], 1e4, pk.dtype)
    pk2 = pk.at[0].set(poison)                    # dummy block
    pv2 = pv.at[0].set(poison)
    # also poison a block allocated to row 1 beyond its position
    live1 = int(pos[1]) // pk.shape[2] + 1
    table2 = table.at[1, live1].set(int(table[2, 0]))
    out = np.asarray(paged_attention_decode(q, pk2, pv2, table2, pos,
                                            interpret=True), np.float32)
    np.testing.assert_allclose(out, base, rtol=1e-6, atol=1e-6)


def _force_schedule(monkeypatch, chunk, heads, hkv, bs, hd, dtype, nbm,
                    quant=False):
    """Shrink the kernel's VMEM budget so that ``page_schedule`` itself
    derives ``(chunk, heads)`` at a test's tiny shapes — several
    chunks a row, a split head grid — and say which fetch style runs."""
    from veles_tpu.ops.pallas import paged
    monkeypatch.setattr(
        paged, "_PAGE_BUFFER_BYTES",
        4 * chunk * heads * paged._head_page_bytes(bs, hd, dtype, quant))
    assert paged.page_schedule(hkv, bs, hd, dtype, nbm, quant) == \
        (chunk, heads)
    return paged._sliceable(hd, quant)


def _schedule_edges(chunk, bs, nbm):
    """The positions the live-page loop can get wrong: the first key,
    either side of the first page boundary and of the first chunk
    boundary, a tail of live pages that does not fill a chunk, the last
    key of a full table; the last row is a free slot."""
    return [0, bs - 1, bs, chunk * bs - 1, chunk * bs,
            (chunk + 1) * bs + 3, (nbm - 1) * bs - 1, nbm * bs - 1, 0]


@pytest.mark.parametrize("g", [1, 4])
@pytest.mark.parametrize("hd", [64, 128])
@pytest.mark.parametrize("bs", [16, 32])
def test_paged_decode_live_page_schedule_matches_reference(
        monkeypatch, bs, hd, g):
    """Kernel == reference over the shapes the live-page schedule can
    get wrong, in both fetch styles (hd 128: pages copied by hand;
    hd 64: BlockSpec'd page operands), 128 keys a chunk and two and a
    half chunks a table."""
    from veles_tpu.ops.pallas.paged import (paged_attention_decode,
                                            paged_attention_reference)
    chunk = 128 // bs
    nbm = 2 * chunk + chunk // 2
    by_hand = _force_schedule(monkeypatch, chunk, 2, 2, bs, hd,
                              jnp.float32, nbm)
    assert by_hand == (hd == 128)
    pos = _schedule_edges(chunk, bs, nbm)
    args = _paged_case(pos, g=g, bs=bs, nbm=nbm, hd=hd,
                       free={len(pos) - 1})
    ref = paged_attention_reference(*args)
    out = paged_attention_decode(*args, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=5e-6, atol=5e-6)


@pytest.mark.parametrize("hd,heads", [(64, 2), (128, 2), (128, 1)])
def test_paged_decode_32_ragged_rows(monkeypatch, hd, heads):
    """32 rows of ragged lengths (the serving cell's row count), bf16,
    with the KV heads whole and split over the grid."""
    from veles_tpu.ops.pallas.paged import (paged_attention_decode,
                                            paged_attention_reference)
    bs, nbm = 16, 12
    _force_schedule(monkeypatch, 8, heads, 2, bs, hd, jnp.bfloat16, nbm)
    pos = np.random.RandomState(3).randint(0, nbm * bs, size=32)
    args = _paged_case(pos, bs=bs, nbm=nbm, hd=hd, dtype=jnp.bfloat16)
    ref = paged_attention_reference(*args)
    out = paged_attention_decode(*args, interpret=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("hd", [64, 128])
def test_paged_decode_visits_live_pages_only(monkeypatch, hd):
    """Every pool page that no live prefix names is poisoned (the dummy
    block too), and every table entry past a row's live prefix points
    at a poisoned page: the output does not move.  Nothing past
    ``pos[b]`` is visited or, if fetched, used."""
    from veles_tpu.ops.pallas.paged import paged_attention_decode
    bs, chunk = 16, 8
    nbm = 2 * chunk + chunk // 2
    _force_schedule(monkeypatch, chunk, 2, 2, bs, hd, jnp.float32, nbm)
    pos = _schedule_edges(chunk, bs, nbm)[:-1]
    q, pk, pv, table, _ = args = _paged_case(pos, bs=bs, nbm=nbm, hd=hd)
    base = np.asarray(paged_attention_decode(*args, interpret=True))
    live = np.asarray(table) > 0
    named = np.zeros(pk.shape[0], bool)
    named[np.asarray(table)[live]] = True
    dead = np.nonzero(~named)[0]
    assert 0 in dead and len(dead) > nbm
    pk2 = pk.at[dead].set(1e4)
    pv2 = pv.at[dead].set(1e4)
    table2 = jnp.where(live, table, jnp.asarray(
        np.resize(dead, live.shape), jnp.int32))
    out = np.asarray(paged_attention_decode(q, pk2, pv2, table2, args[4],
                                            interpret=True))
    np.testing.assert_allclose(out, base, rtol=1e-6, atol=1e-6)
