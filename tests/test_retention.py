"""``ops.retention``: power retention of degree 2 in its three forms —
the recurrence (one token a row; its Pallas kernel in interpret mode),
the chunked pass that hands a state on, and the whole-sequence form —
against the plain quadratic form written out here, at small sizes on
the CPU, seeded.

Tolerances: everything is float32; what differs between the forms is
the order of float32 sums and, through the state, ``exp(b_t) S`` where
the quadratic form has ``exp(b_t - b_s)`` — relative 1e-6 a term on
outputs of magnitude 1: ``atol=2e-4`` and ``rtol=2e-4`` (the recurrences
reach 4e-5).  One case has its own: with gates near 0 a query's
denominator is its own key's weight alone, and where that ``(q . k)^2``
is 1e-4 the features' sum (rounding relative to ``|q|^2 |k|^2``) has
lost three digits of it — 7e-4 on one output in two hundred, so
``atol=2e-3`` there; a gate of a half-life of tokens, as every served
model's, sums many weights and stays at 4e-5."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from veles_tpu.ops import retention as R

B, HQ, HKV, HD, T = 2, 6, 2, 16, 24
ATOL = 2e-4


def _inputs(seed=0, gates=(0.05, 0.999)):
    rng = np.random.default_rng(seed)

    def a(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    logg = jnp.asarray(np.log(rng.uniform(*gates, size=(B, HKV, T))),
                       jnp.float32)
    return a(B, HQ, T, HD), a(B, HKV, T, HD), a(B, HKV, T, HD), logg


def quadratic(q, k, v, logg):
    """y_t = sum_s a_ts v_s / (sum_s a_ts + eps), a_ts = exp(sum_{r in
    (s, t]} log g_r) ((q_t . k_s) / sqrt hd)^2 — every pair, no state."""
    g = HQ // HKV
    k, v, logg = (jnp.repeat(a, g, axis=1) for a in (k, v, logg))
    cum = jnp.cumsum(logg, axis=-1)
    score = jnp.einsum("bhtd,bhsd->bhts", q, k,
                       precision="highest") / np.sqrt(HD)
    t = q.shape[2]
    mask = jnp.tril(jnp.ones((t, t), bool))
    decay = jnp.exp(jnp.where(mask, cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))
    w = jnp.square(score) * decay
    return jnp.einsum("bhts,bhsd->bhtd", w, v, precision="highest") \
        / (jnp.sum(w, axis=-1)[..., None] + R.EPS)


@pytest.mark.parametrize("hd", [2, 8, 16, 128])
def test_phi_is_the_symmetric_second_power(hd):
    rng = np.random.default_rng(hd)
    a, b = (jnp.asarray(rng.normal(size=(7, hd)), jnp.float32)
            for _ in range(2))
    fa, fb = R.phi(a), R.phi(b)
    assert fa.shape == (7, R.phi_width(hd))
    assert R.phi_width(hd) % 128 == 0 \
        and R.phi_width(hd) - hd * (hd + 1) // 2 < 128
    # a sum of hd (hd + 1) / 2 float32 products: its rounding is relative
    # to |a|^2 |b|^2, not to a (a . b)^2 that cancellation made small
    scale = float(np.max(np.sum(a * a, -1) * np.sum(b * b, -1)))
    np.testing.assert_allclose(np.sum(fa * fb, -1),
                               np.sum(a * b, -1) ** 2, rtol=2e-5,
                               atol=1e-6 * scale)
    # the padding holds nothing
    assert not np.asarray(fa[:, hd * (hd + 1) // 2:]).any()


def test_the_layout_at_hd_128_stays_under_the_state_budget():
    assert R.phi_width(128) == 8320 <= 9216
    assert R.state_shapes(8, 128) == {"s": (8, 128, 8320), "z": (8, 8320)}


@pytest.mark.parametrize("chunk", [24, 12, 8, 4, 1])
@pytest.mark.parametrize("gates", [(0.05, 0.999), (1e-4, 1e-3),
                                   (0.9999, 1.0)],
                         ids=["mixed", "near_0", "near_1"])
def test_chunked_and_quadratic_forms_agree(chunk, gates):
    q, k, v, logg = _inputs(gates=gates)
    y, _ = R.retention_chunk(q, k, v, logg, chunk=chunk)
    np.testing.assert_allclose(y, quadratic(q, k, v, logg), rtol=2e-4, atol=ATOL)


@pytest.mark.parametrize("gates,atol", [((0.05, 0.999), ATOL),
                                        ((1e-4, 1e-3), 2e-3),
                                        ((0.9999, 1.0), ATOL)],
                         ids=["mixed", "near_0", "near_1"])
def test_step_and_quadratic_forms_agree(gates, atol):
    q, k, v, logg = _inputs(gates=gates)
    state, outs = R.init_state(B, HKV, HD), []
    for t in range(T):
        y, state = R.retention_step(q[:, :, t], k[:, :, t], v[:, :, t],
                                    logg[..., t], state)
        outs.append(y)
    np.testing.assert_allclose(jnp.stack(outs, 2), quadratic(q, k, v, logg),
                               rtol=2e-4, atol=atol)


@pytest.mark.parametrize("cut,chunk", [(16, 8), (8, 8), (12, 4), (20, 4)])
def test_a_pass_boundary_inside_a_prompt_hands_the_state_on(cut, chunk):
    q, k, v, logg = _inputs(1)
    y1, state = R.retention_chunk(q[:, :, :cut], k[:, :, :cut],
                                  v[:, :, :cut], logg[..., :cut],
                                  chunk=chunk)
    y2, _ = R.retention_chunk(q[:, :, cut:], k[:, :, cut:], v[:, :, cut:],
                              logg[..., cut:], state, chunk=chunk)
    np.testing.assert_allclose(jnp.concatenate([y1, y2], 2),
                               quadratic(q, k, v, logg), rtol=2e-4, atol=ATOL)


@pytest.mark.parametrize("valid", [0, 5, 13, 16])
def test_only_the_valid_tokens_of_a_pass_enter_the_state(valid):
    """A pass of 16 tokens of which ``valid`` count (the rest padding,
    or the token the decode step takes again), then the recurrence from
    position ``valid`` on: the whole is the quadratic form's."""
    q, k, v, logg = _inputs(2)
    y1, state = R.retention_chunk(q[:, :, :16], k[:, :, :16], v[:, :, :16],
                                  logg[..., :16], chunk=8, valid=valid)
    outs = []
    for t in range(valid, T):
        y, state = R.retention_step(q[:, :, t], k[:, :, t], v[:, :, t],
                                    logg[..., t], state)
        outs.append(y)
    want = quadratic(q, k, v, logg)
    np.testing.assert_allclose(y1[:, :, :valid], want[:, :, :valid],
                               rtol=2e-4, atol=ATOL)
    np.testing.assert_allclose(jnp.stack(outs, 2), want[:, :, valid:],
                               rtol=2e-4, atol=ATOL)


@pytest.fixture
def kernel():
    prev, R.KERNEL = R.KERNEL, True
    try:
        yield
    finally:
        R.KERNEL = prev


@pytest.mark.parametrize("active", [[True, True], [True, False],
                                    [False, True], [False, False]],
                         ids=["both", "first", "second", "none"])
def test_the_kernel_is_the_step_and_skips_an_idle_row(kernel, active):
    q, k, v, logg = _inputs(3)
    _, state = R.retention_chunk(q[:, :, :13], k[:, :, :13], v[:, :, :13],
                                 logg[..., :13])
    args = (q[:, :, 13], k[:, :, 13], v[:, :, 13], logg[..., 13], state)
    on = jnp.asarray(active)
    y, new = R.retention_step_rows(*args, active=on)
    want_y, want = R.retention_step(*args)
    rows = on[:, None, None]
    np.testing.assert_allclose(y, jnp.where(rows, want_y, 0.0), atol=1e-5)
    np.testing.assert_allclose(
        new.s, jnp.where(rows[..., None], want.s, state.s), atol=1e-5)
    np.testing.assert_allclose(new.z, jnp.where(rows, want.z, state.z),
                               atol=1e-5)
    # an idle row's state is the same bits
    for i, flag in enumerate(active):
        if not flag:
            assert (np.asarray(new.s[i]) == np.asarray(state.s[i])).all()


def test_the_kernel_tiles_the_features_of_hd_128(kernel):
    """Five tiles of 1,664 lanes a KV head, two query heads a group."""
    from veles_tpu.ops.pallas import retention as kernel_module
    assert kernel_module.tile_lanes(8320) == 1664
    rng = np.random.default_rng(5)
    b, hq, hkv, hd = 2, 4, 2, 128
    q, k, v = (jnp.asarray(rng.normal(size=(b, n, hd)), jnp.float32)
               for n in (hq, hkv, hkv))
    logg = jnp.asarray(np.log(rng.uniform(0.5, 1, (b, hkv))), jnp.float32)
    state = R.RetentionState(
        jnp.asarray(rng.normal(size=(b, hkv, hd, 8320)), jnp.float32),
        jnp.asarray(rng.uniform(5, 6, size=(b, hkv, 8320)), jnp.float32))
    y, new = R.retention_step_rows(q, k, v, logg, state)
    want_y, want = R.retention_step(q, k, v, logg, state)
    np.testing.assert_allclose(y, want_y, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(new.s, want.s, atol=1e-4)


def test_gradients_of_the_whole_sequence_form_are_the_quadratic_forms():
    q, k, v, logg = _inputs(4)

    def loss(form):
        return lambda q, k, v, logg: jnp.sum(jnp.square(
            form(q, k, v, logg)))

    chunked = jax.grad(loss(lambda *a: R.retention_chunk(*a, chunk=8)[0]),
                       argnums=(0, 1, 2, 3))(q, k, v, logg)
    plain = jax.grad(loss(quadratic), argnums=(0, 1, 2, 3))(q, k, v, logg)
    for got, want in zip(chunked, plain):
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def test_a_pass_that_does_not_divide_into_chunks_is_refused():
    q, k, v, logg = _inputs()
    with pytest.raises(ValueError, match="does not divide"):
        R.retention_chunk(q, k, v, logg, chunk=7)
