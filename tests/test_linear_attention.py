"""ops/linear_attention.py on the CPU at tiny sizes: the chunked gated
delta rule against a token-by-token scan (values and every gradient,
several chunks, a length that is no multiple of the chunk, decays near
0 and near 1, the head groups), the triangular inverse where keys
repeat, the intra-chunk part's closed-form derivative against the
plainly differentiated rule, the Pallas kernels (interpreted) against
the `jax.numpy` form, the causal convolution against explicit shifts,
and the `linearAttention` profiler section."""
from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp


def _recurrent(q, k, v, g, beta):
    """The rule one token at a time, float32, state from 0."""
    b, h, _, dv = v.shape
    r = h // k.shape[1]
    q, k = (jnp.repeat(x, r, 1).astype(jnp.float32) for x in (q, k))
    v, g, beta = (x.astype(jnp.float32) for x in (v, g, beta))

    def token(state, x):
        q_t, k_t, v_t, g_t, beta_t = x
        state = state * jnp.exp(g_t)[..., None, None]
        d = beta_t[..., None] * (v_t - jnp.einsum(
            "bhkv,bhk->bhv", state, k_t, precision="highest"))
        state = state + k_t[..., :, None] * d[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t,
                                 precision="highest")

    xs = [jnp.moveaxis(x, 2, 0) for x in (q, k, v, g, beta)]
    _, o = jax.lax.scan(token, jnp.zeros((b, h, k.shape[-1], dv)), xs)
    return jnp.moveaxis(o, 0, 2)


def _inputs(b=2, hk=2, hv=4, seq=200, dk=16, dv=24, seed=0,
            dtype=jnp.float32):
    rng = np.random.RandomState(seed)

    def unit(x):
        return x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)

    q = unit(rng.randn(b, hk, seq, dk)) / np.sqrt(dk)
    # keys that lean one way: neighbours overlap, the triangular
    # system is far from the identity
    k = unit(rng.randn(b, hk, seq, dk) + 1.5)
    v = rng.randn(b, hv, seq, dv)
    g = -np.exp(rng.randn(b, hv, seq) * 2 - 1)
    g[:, 0] = -1e-4         # a head that forgets nothing
    g[:, 1] = -30.0         # a head that forgets everything, every token
    beta = 1 / (1 + np.exp(-rng.randn(b, hv, seq) * 2))
    return (jnp.asarray(q, dtype), jnp.asarray(k, dtype),
            jnp.asarray(v, dtype), jnp.asarray(g, jnp.float32),
            jnp.asarray(beta, jnp.float32))


def _weighted(fn, *args):
    o = fn(*args).astype(jnp.float32)
    return (o * jnp.cos(jnp.arange(o.size, dtype=jnp.float32)
                        .reshape(o.shape))).sum()


@pytest.mark.parametrize("shape", [
    dict(), dict(seq=128), dict(seq=65, hv=2),
    dict(b=4, hk=4, hv=8, seq=96)],
    ids=["200_padded_to_four_chunks", "two_chunks", "one_token_past_a_chunk",
         "two_head_groups"])
def test_chunked_rule_matches_the_token_scan_values_and_gradients(shape):
    """float32: the two forms are one function, so they agree to
    round-off: 1e-5 of the largest value (a bf16 state, 4e-3, or a
    decay left out would fail by orders); a length that is no multiple
    of the chunk is padded, never cut."""
    from mxnet_tpu.ops import linear_attention as la

    args = _inputs(**shape)
    if "b" in shape:
        assert la.head_groups(4, 4, 8) == 2
    got = la._k_gated_delta_rule(*args)
    want = _recurrent(*args)
    assert got.shape == want.shape == args[2].shape
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) < 1e-5 * scale
    # the last tokens, past the last whole chunk, are computed
    assert float(jnp.abs(got[:, :, -1]).max()) > 0
    grads = [jax.grad(lambda *a, fn=fn: _weighted(fn, *a),
                      argnums=(0, 1, 2, 3, 4))(*args)
             for fn in (la._k_gated_delta_rule, _recurrent)]
    for name, g, w in zip("q k v g beta".split(), *grads):
        assert float(jnp.abs(g - w).max()) < 2e-5 * float(jnp.abs(w).max()), \
            name


def test_chunked_rule_in_bfloat16_stays_near_the_float32_scan():
    """bf16 operands, float32 state and accumulation: 2 % of the
    largest value (one bf16 rounding of each operand is 0.4 %, several
    products deep); the state itself in bf16 over 200 tokens reads 5 %
    and more."""
    from mxnet_tpu.ops import linear_attention as la

    args = _inputs(dtype=jnp.bfloat16)
    got = la._k_gated_delta_rule(*args).astype(jnp.float32)
    want = _recurrent(*args)
    assert got.dtype == jnp.float32 and jnp.isfinite(got).all()
    assert float(jnp.abs(got - want).max()) < 2e-2 * float(
        jnp.abs(want).max())
    grad = jax.grad(lambda *a: _weighted(la._k_gated_delta_rule, *a),
                    argnums=(0, 1, 2, 3, 4))(*args)
    want = jax.grad(lambda *a: _weighted(_recurrent, *a),
                    argnums=(0, 1, 2, 3, 4))(*args)
    for name, g, w in zip("q k v g beta".split(), grad, want):
        assert jnp.isfinite(g.astype(jnp.float32)).all(), name
        assert float(jnp.abs(g.astype(jnp.float32) - w).max()) < 5e-2 * float(
            jnp.abs(w).max()), name


def test_strong_decay_overflows_nothing():
    """g = -80 a token: exp(-80 * 64) is 0 and its reciprocal would be
    inf; only differences g_i - g_j <= 0 are ever exponentiated."""
    from mxnet_tpu.ops import linear_attention as la

    q, k, v, g, beta = _inputs(seq=128)
    g = jnp.full_like(g, -80.0)
    out, grads = jax.value_and_grad(
        lambda *a: _weighted(la._k_gated_delta_rule, *a),
        argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)
    assert np.isfinite(float(out))
    assert all(bool(jnp.isfinite(x).all()) for x in grads)
    want = _recurrent(q, k, v, g, beta)
    got = la._k_gated_delta_rule(q, k, v, g, beta)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


def test_unit_lower_inverse_is_exact_where_keys_repeat():
    """All keys equal and beta 1: A is the strictly lower matrix of
    ones, whose 64-step Neumann series cancels binomial coefficients
    of 1e18; by blocks of 16 the inverse (the bidiagonal 1, -1) is
    exact."""
    from mxnet_tpu.ops.linear_attention import _unit_lower_inverse

    a = jnp.tril(jnp.ones((64, 64), jnp.float32), -1)
    np.testing.assert_array_equal(
        np.asarray(_unit_lower_inverse(a)), np.eye(64) - np.eye(64, k=-1))
    a = jnp.tril(jnp.asarray(np.random.RandomState(1).rand(3, 64, 64),
                             jnp.float32), -1)
    product = jnp.matmul(_unit_lower_inverse(a), jnp.eye(64) + a,
                         precision="highest")
    np.testing.assert_allclose(np.asarray(product),
                               np.broadcast_to(np.eye(64), (3, 64, 64)),
                               atol=2e-4)


def _rule_plainly_differentiated(q, k, v, g, beta):
    """The chunked rule as it stood before its intra-chunk part had a
    derivative of its own: JAX differentiates through everything, the
    inverse's ten products included.  The plain reference for
    `_wy_xla`."""
    from mxnet_tpu.ops.linear_attention import CHUNK, _unit_lower_inverse

    b, h, seq, dv = v.shape
    dk, dtype, n = k.shape[-1], v.dtype, seq // CHUNK
    q, k = (jnp.repeat(x, h // k.shape[1], axis=1) for x in (q, k))

    def dot(spec, x, y):
        return jnp.einsum(spec, x.astype(dtype), y.astype(dtype),
                          precision="highest",
                          preferred_element_type=jnp.float32)

    q, k, v = (x.reshape(b, h, n, CHUNK, x.shape[-1]) for x in (q, k, v))
    beta = beta.astype(jnp.float32).reshape(b, h, n, CHUNK)
    total = jnp.cumsum(g.astype(jnp.float32).reshape(b, h, n, CHUNK), -1)
    seen = jnp.tril(jnp.ones((CHUNK, CHUNK), bool))
    decay = jnp.where(seen, jnp.exp(jnp.where(
        seen, total[..., :, None] - total[..., None, :], 0.0)), 0.0)
    strict = jnp.tril(jnp.ones((CHUNK, CHUNK), bool), -1)
    a = jnp.where(strict, beta[..., None] * decay
                  * dot("...id,...jd->...ij", k, k), 0.0)
    inverse = _unit_lower_inverse(a)
    into = jnp.exp(total)[..., None]
    u = dot("...ij,...jd->...id", inverse, v * beta[..., None])
    w = dot("...ij,...jd->...id", inverse, k * (beta[..., None] * into))
    within = decay * dot("...id,...jd->...ij", q, k)
    k_out = k * jnp.exp(total[..., -1:] - total)[..., None]

    def step(state, xs):
        u, w, within, q_in, k_out, last = xs
        d = u - dot("...id,...de->...ie", w, state)
        o = dot("...id,...de->...ie", q_in, state) \
            + dot("...ij,...je->...ie", within, d)
        state = state * last[..., None, None] \
            + dot("...id,...ie->...de", k_out, d)
        return state, o.astype(dtype)

    by_chunk = [jnp.moveaxis(x, 2, 0) for x in (
        u, w.astype(dtype), within.astype(dtype), (q * into).astype(dtype),
        k_out.astype(dtype), jnp.exp(total[..., -1]))]
    _, o = jax.lax.scan(step, jnp.zeros((b, h, dk, dv), jnp.float32),
                        by_chunk)
    return jnp.moveaxis(o, 0, 2).reshape(b, h, seq, dv)


def _case(name, dtype, **shape):
    """Inputs that lean on one part of the derivative each."""
    q, k, v, g, beta = _inputs(dtype=dtype, **shape)
    if name == "strong_decay":
        g = jnp.full_like(g, -40.0).at[:, ::2, ::3].set(-0.5)
    elif name == "repeated_keys":
        k = jnp.broadcast_to(k[:, :, :1], k.shape)
        beta = jnp.full_like(beta, 0.9)
    return q, k, v, g, beta


@pytest.mark.parametrize("dtype,tolerance,ill_conditioned", [
    ("float32", 2e-5, 1e-2), ("bfloat16", 4e-2, 1e-1)])
@pytest.mark.parametrize("name", ["mixed", "strong_decay", "repeated_keys"])
def test_closed_form_derivative_matches_the_plainly_differentiated_rule(
        name, dtype, tolerance, ill_conditioned):
    """`_wy_xla`'s backward is the same function's derivative: against
    `jax.value_and_grad` THROUGH the inverse's products, the value and
    all five gradients agree to float32 round-off (a missing term of
    dG, or dA's sign, fails by the gradient's own size); in bf16 to
    the rounding of the cotangents' operands, a few products deep.  2
    key heads under 4 value heads: dq and dk sum over the pair.  Where
    every key is the same the triangular system is ill-conditioned:
    the two float32 derivatives sit 2e-3 and 3e-3 of dg's size from
    the token scan's, 5e-3 from each other."""
    from mxnet_tpu.ops import linear_attention as la
    from mxnet_tpu.ops.pallas import delta_rule

    args = _case(name, dtype, seq=192)
    assert not delta_rule.admits(*args[:3])
    (got, got_grads), (want, want_grads) = (
        jax.value_and_grad(lambda *a, fn=fn: _weighted(fn, *a),
                           argnums=(0, 1, 2, 3, 4))(*args)
        for fn in (la._rule, _rule_plainly_differentiated))
    assert np.isfinite(float(got))
    assert abs(float(got) - float(want)) <= 1e-6 * abs(float(want))
    for which, g, w in zip("q k v g beta".split(), got_grads, want_grads):
        assert g.dtype == w.dtype and g.shape == w.shape, which
        g, w = g.astype(jnp.float32), w.astype(jnp.float32)
        assert float(jnp.abs(w).max()) > 0, which
        assert float(jnp.abs(g - w).max()) < (
            ill_conditioned if name == "repeated_keys" else tolerance
        ) * float(jnp.abs(w).max()), which


@pytest.mark.parametrize("dtype,tolerance", [
    ("float32", 1e-5), ("bfloat16", 2e-3)])
def test_kernels_match_the_jnp_form_interpreted(interpret_pallas, dtype,
                                                tolerance):
    """Both kernels of ops/pallas/delta_rule.py against `_wy_xla` at dk
    = dv = 128, two pairs of chunks, one key head under two value
    heads: the six outputs (shape, dtype, chunk axis first) and the
    five gradients for random cotangents.  The two share every
    rounding, so float32 agrees to round-off and bf16 but for a last
    bit of an operand (a lost mask or a block of the pair placed in
    the other chunk fails by 1)."""
    from mxnet_tpu.ops import linear_attention as la
    from mxnet_tpu.ops.pallas import delta_rule

    args = _case("mixed", dtype, b=1, hk=1, hv=2, seq=256, dk=128, dv=128)
    assert delta_rule.admits(*args[:3])
    got, got_vjp = jax.vjp(delta_rule.wy, *args)
    want, want_vjp = jax.vjp(la._wy_xla, *args)
    rng = np.random.RandomState(5)
    cotangents = tuple(jnp.asarray(rng.randn(*x.shape), x.dtype)
                       for x in want)

    def near(name, g, w):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        g, w = g.astype(jnp.float32), w.astype(jnp.float32)
        assert float(jnp.abs(g - w).max()) <= tolerance * float(
            jnp.abs(w).max()), name

    assert want[0].shape == (4, 1, 2, 64, 128)
    for name, g, w in zip("u w within q_in k_out last".split(), got, want):
        near(name, g, w)
    for name, g, w in zip("q k v g beta".split(), got_vjp(cotangents),
                          want_vjp(cotangents)):
        near("d" + name, g, w)


def test_a_float32_cotangent_goes_into_a_bf16_product_whole():
    """The scan kernels' `_product`: a float32 operand against a bf16
    one goes in as three bf16 parts that sum to it exactly, so the
    product is the float32 product that JAX's derivative takes
    (`precision="highest"`) to round-off; one bf16 pass of the same
    operands is 1e-3 off."""
    from mxnet_tpu.ops.pallas import delta_rule

    rng = np.random.RandomState(6)
    x = jnp.asarray(rng.randn(3, 64, 128), jnp.float32)
    y = jnp.asarray(rng.randn(3, 128, 256), jnp.bfloat16)
    parts = delta_rule._parts(x, jnp.bfloat16)
    assert [p.dtype for p in parts] == [jnp.bfloat16] * 3
    np.testing.assert_array_equal(
        np.asarray(sum(p.astype(jnp.float32) for p in parts)), np.asarray(x))
    want = jnp.einsum("pij,pjk->pik", x, y.astype(jnp.float32),
                      precision="highest")
    scale = float(jnp.abs(want).max())
    got = delta_rule._product(jnp.bfloat16, x, y)
    assert float(jnp.abs(got - want).max()) < 1e-6 * scale
    one_pass = delta_rule._product(jnp.bfloat16, x.astype(jnp.bfloat16), y)
    assert float(jnp.abs(one_pass - want).max()) > 1e-3 * scale
    # the other operand transposed, and a float32 pair alone
    got = delta_rule._product(jnp.bfloat16, y, x[:, :, :128], 1, 2)
    want = jnp.einsum("pji,pkj->pik", y.astype(jnp.float32), x[:, :, :128],
                      precision="highest")
    assert got.shape == (3, 256, 64)
    assert float(jnp.abs(got - want).max()) < 1e-6 * float(
        jnp.abs(want).max())
    assert len(delta_rule._parts(x, jnp.float32)) == 1


def _scan_case(name, dtype, seq=1280, dk=128, dv=256):
    """What `_wy_xla` hands the chunk scan, for one key head under two
    value heads: 20 chunks (blocks of 5 a grid step: the state crosses
    three grid steps) at dk != dv."""
    from mxnet_tpu.ops import linear_attention as la

    return la._wy_xla(*_case(name, dtype, b=1, hk=1, hv=2, seq=seq, dk=dk,
                             dv=dv))


@pytest.mark.parametrize("dtype,tolerance", [
    ("float32", 1e-5), ("bfloat16", 1e-2)])
@pytest.mark.parametrize("name,side", [
    ("mixed", 16), ("strong_decay", 16), ("mixed", 1)],
    ids=["pairs_side_by_side", "strong_decay", "a_pair_a_grid_step"])
def test_scan_kernels_match_the_lax_scan_interpreted(
        interpret_pallas, monkeypatch, dtype, tolerance, name, side):
    """The chunk scan's kernel pair (`delta_rule.scan`) against
    `_scan_xla`: o and `jax.vjp` of all six inputs for random
    cotangents, at key size 128 under value size 256, over several
    chunk blocks a pair (and several pair blocks at one pair a grid
    step), so the VMEM state and its cotangent cross grid steps.  The
    two share every rounding: float32 agrees to round-off; in bf16 o
    agrees and the gradients but for a bf16 rounding of a cotangent
    that the float32 products' order of summation moved across a last
    bit, carried along the chunks (4e-3 of the largest over 20)."""
    from mxnet_tpu.ops import linear_attention as la
    from mxnet_tpu.ops.pallas import delta_rule

    monkeypatch.setattr(delta_rule, "_SCAN_PAIRS", side)
    xs = _scan_case(name, dtype)
    n, b, h, _, dv = xs[0].shape
    cut = delta_rule._Scan(delta_rule._by_pair(xs[0]),
                           delta_rule._by_pair(xs[1]), "backward")
    assert (cut.side, cut.chunks, cut.blocks) == (min(side, 2), 5, 4)
    got, got_vjp = jax.vjp(delta_rule.scan, *xs)
    want, want_vjp = jax.vjp(la._scan_xla, *xs)
    assert want.shape == (1, 2, 1280, 256) and want.dtype == xs[1].dtype
    rng = np.random.RandomState(7)
    cotangent = jnp.asarray(rng.randn(*want.shape), want.dtype)

    def near(which, g, w):
        assert g.shape == w.shape and g.dtype == w.dtype, which
        g, w = g.astype(jnp.float32), w.astype(jnp.float32)
        assert float(jnp.abs(w).max()) > 0, which
        assert float(jnp.abs(g - w).max()) <= tolerance * float(
            jnp.abs(w).max()), which

    near("o", got, want)
    for which, g, w in zip("u w within q_in k_out last".split(),
                           got_vjp(cotangent), want_vjp(cotangent)):
        near("d" + which, g, w)


def test_rule_with_every_kernel_matches_the_token_scan(monkeypatch,
                                                       interpret_pallas):
    """The rule as the TPU runs it (both branches of its
    `lax.platform_dependent`s the TPU's, every kernel interpreted):
    values and all five gradients against the token-by-token scan, as
    the chunked form is held above; 200 tokens padded to four chunks,
    one key head under two value heads of 128, float32."""
    from mxnet_tpu.ops import linear_attention as la
    from mxnet_tpu.ops.pallas import delta_rule

    monkeypatch.setattr(jax.lax, "platform_dependent",
                        lambda *args, tpu, default: tpu(*args))
    calls = []
    scan = delta_rule.scan
    monkeypatch.setattr(delta_rule, "scan",
                        lambda *xs: calls.append(1) or scan(*xs))
    args = _inputs(b=1, hk=1, hv=2, seq=200, dk=128, dv=128)
    got = la._k_gated_delta_rule(*args)
    want = _recurrent(*args)
    assert calls and got.shape == want.shape == args[2].shape
    assert float(jnp.abs(got - want).max()) < 1e-5 * float(
        jnp.abs(want).max())
    grads = [jax.grad(lambda *a, fn=fn: _weighted(fn, *a),
                      argnums=(0, 1, 2, 3, 4))(*args)
             for fn in (la._k_gated_delta_rule, _recurrent)]
    for name, g, w in zip("q k v g beta".split(), *grads):
        assert float(jnp.abs(g - w).max()) < 2e-5 * float(jnp.abs(w).max()), \
            name


def test_kernels_take_whole_lanes_and_pairs_of_chunks_only():
    from mxnet_tpu.ops.pallas import delta_rule

    def admits(seq=256, dk=128, dv=128, dtype=jnp.bfloat16):
        q = jax.ShapeDtypeStruct((2, 2, seq, dk), dtype)
        return delta_rule.admits(
            q, q, jax.ShapeDtypeStruct((2, 4, seq, dv), dtype))

    assert admits() and admits(dtype=jnp.float32) and admits(dk=256)
    assert not admits(seq=192) and not admits(dk=64) and not admits(dv=96)
    assert not admits(dtype=jnp.float16)


def test_value_heads_must_be_a_multiple_of_key_heads():
    from mxnet_tpu.ops import linear_attention as la

    q, k, v, g, beta = _inputs(hk=3, hv=4)
    with pytest.raises(ValueError, match="4 value heads over 3"):
        la._k_gated_delta_rule(q, k, v, g, beta)


@pytest.mark.parametrize("dtype,tolerance", [
    ("float32", 1e-5), ("bfloat16", 2e-2)])
def test_causal_conv1d_matches_explicit_shifts_and_leaks_nothing(dtype,
                                                                 tolerance):
    """SiLU of four explicit shifts; in bf16 the taps are summed in
    float32 and the result rounded once (0.4 %), on inputs rounded to
    bf16 beforehand."""
    import mxnet_tpu as mx

    rng = np.random.RandomState(3)
    x = mx.nd.array(rng.randn(2, 12, 6)).astype(dtype)
    w = mx.nd.array(rng.randn(6, 4)).astype(dtype)
    got = mx.nd.causal_conv1d(x, w)
    assert got.dtype == x.dtype
    got = got.astype("float32").asnumpy()
    x, w = x.astype("float32").asnumpy(), w.astype("float32").asnumpy()
    want = np.zeros_like(x)
    for t in range(12):
        for j in range(4):          # tap j reads position t - 3 + j
            if t - 3 + j >= 0:
                want[:, t] += w[:, j] * x[:, t - 3 + j]
    want = want / (1 + np.exp(-want))
    np.testing.assert_allclose(got, want, rtol=tolerance, atol=tolerance)
    # nothing from t + 1 reaches t: perturb token 7, tokens 0-6 stay
    later = x.copy()
    later[:, 7] += 10.0
    moved = mx.nd.causal_conv1d(
        mx.nd.array(later).astype(dtype),
        mx.nd.array(w).astype(dtype)).astype("float32").asnumpy()
    np.testing.assert_array_equal(moved[:, :7], got[:, :7])
    assert np.abs(moved[:, 7:11] - got[:, 7:11]).min() > 0
    np.testing.assert_array_equal(moved[:, 11], got[:, 11])


def test_the_trainers_remat_keeps_what_the_ops_name():
    """One list of the names a `jax.checkpoint` policy keeps
    (`ops.registry.RESIDUAL_NAMES`): each op names its outputs from it,
    and `DataParallelTrainer(remat=True)` saves them all, without
    naming an op."""
    from mxnet_tpu.ops import linear_attention as la
    from mxnet_tpu.ops import registry
    from mxnet_tpu.ops.pallas import flash_attention
    from mxnet_tpu.parallel import data_parallel

    assert la.RESIDUAL_NAMES == ("delta_rule_out",)
    assert registry.RESIDUAL_NAMES == {
        "flash_attention": flash_attention.RESIDUAL_NAMES,
        "gated_delta_rule": la.RESIDUAL_NAMES}
    assert set(data_parallel._remat_saves()) == {
        "flash_out", "flash_lse", "delta_rule_out"}
    jaxpr = jax.make_jaxpr(la._k_gated_delta_rule)(*_inputs(seq=64))
    assert "name=delta_rule_out" in str(jaxpr)


def test_l2_norm_and_the_norms_new_forms():
    import mxnet_tpu as mx

    rng = np.random.RandomState(4)
    x = rng.randn(3, 5, 16).astype(np.float32)
    gamma = rng.randn(16).astype(np.float32) * 0.1
    gate = rng.randn(3, 5, 16).astype(np.float32)
    np.testing.assert_allclose(
        mx.nd.l2_norm(mx.nd.array(x), eps=1e-6).asnumpy(),
        x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6), rtol=1e-5)
    x_hat = x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(
        mx.nd.rms_norm(mx.nd.array(x), mx.nd.array(gamma), eps=1e-6,
                       zero_centered=True).asnumpy(),
        x_hat * (1 + gamma), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        mx.nd.gated_rms_norm(mx.nd.array(x), mx.nd.array(gate),
                             mx.nd.array(gamma), eps=1e-6).asnumpy(),
        x_hat * gamma * gate / (1 + np.exp(-gate)), rtol=1e-5, atol=1e-6)


def test_linear_attention_section_is_on_metrics():
    from mxnet_tpu import profiler
    from mxnet_tpu.ops import linear_attention as la
    from mxnet_tpu.telemetry import metrics

    assert "linearAttention" in profiler.section_names()
    profiler.sections(reset=True)
    assert profiler.sections()["linearAttention"]["layers"] == 0
    args = _inputs(seq=200)
    la._k_gated_delta_rule(*args)
    la._k_gated_delta_rule(*args)
    stats = profiler.sections()["linearAttention"]
    key = "b2 h4 s200 k16 v24 float32"
    assert stats == la.linear_attention_stats()
    assert stats["layers"] == 1 and stats["traces"] == {key: 2}
    # heads of 16 and 24 fill no lane: the `jax.numpy` form
    assert stats["kernel_traces"] == {key: 0}
    assert stats["scan_kernel_traces"] == {key: 0}
    assert stats["xla_traces"] == {key: 2}
    assert stats["chunk"][key] == 64
    assert stats["chunks_per_sequence"][key] == 4       # 200 padded to 256
    assert stats["state_bytes_kept"][key] == 4 * 2 * 4 * 16 * 24 * 4
    text = metrics.default_registry().render()
    assert 'mxtpu_linear_attention_state_bytes_kept{key="' + key in text
    assert "mxtpu_linear_attention_layers 1" in text
    assert 'mxtpu_linear_attention_xla_traces{key="' + key + '"} 2' in text
    # whole lanes, an even number of chunks (200 padded to 256): the
    # kernels' shapes, counted whatever the platform lowered for
    wide = _inputs(b=1, hk=1, hv=2, seq=200, dk=128, dv=128)
    la._k_gated_delta_rule(*wide)
    stats = la.linear_attention_stats()
    wide_key = "b1 h2 s200 k128 v128 float32"
    assert stats["kernel_traces"][wide_key] == 1
    assert stats["scan_kernel_traces"][wide_key] == 1
    assert stats["scan_kernel_traces"][key] == 0
    assert stats["xla_traces"][wide_key] == 0
    assert "(1 of shapes the kernels take, 1 with the scan's)" in "\n".join(
        profiler._section_tables())
    assert 'mxtpu_linear_attention_scan_kernel_traces{key="' + wide_key \
        + '"} 1' in metrics.default_registry().render()
    stats = profiler.sections()["linearAttention"]
    table = "\n".join(profiler._section_tables())
    assert "Linear Attention" in table and "4 chunks of 64" in table
    assert profiler.sections(reset=True)["linearAttention"] == stats
    assert profiler.sections()["linearAttention"]["layers"] == 0
