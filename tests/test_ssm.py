"""The Mamba-2 state-space scan (ops/ssm.py) against its per-token
recurrence, values and gradients, at small sizes on the CPU: two chunk
counts, groups shared by heads, the skip, fast decays and slow ones
whose state crosses the chunks, a length that is no multiple of the
chunk; the bf16 form near the float32 one; what `remat` keeps; the
`stateSpace` section.  The kernel pair of ops/pallas/ssd.py,
interpreted, against the `jax.numpy` form and the recurrence, and the
shapes it takes."""
from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp


def _recurrence(x, dt, a, b, c, d):
    """S_t = exp(dt_t a) S + dt_t x_t B_t^T; y_t = S_t C_t + d x_t,
    token by token in float32."""
    batch, seq, heads, p = x.shape
    groups, n = b.shape[2], b.shape[3]
    r = heads // groups
    b, c = (jnp.repeat(v, r, axis=2) for v in (b, c))

    def token(state, xs):
        x_t, dt_t, b_t, c_t = xs
        state = state * jnp.exp(dt_t * a)[..., None, None] \
            + (dt_t[..., None] * x_t)[..., :, None] * b_t[..., None, :]
        y = jnp.einsum("bhpn,bhn->bhp", state, c_t, precision="highest")
        return state, y + d[:, None] * x_t

    _, y = jax.lax.scan(token, jnp.zeros((batch, heads, p, n)),
                        [jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c)])
    return jnp.moveaxis(y, 0, 1)


def _ssd(*args, chunk):
    from mxnet_tpu.ops.ssm import _k_ssd

    return jax.jit(_k_ssd, static_argnames="chunk")(*args, chunk=chunk)


def _inputs(batch=2, seq=96, heads=4, p=8, groups=2, n=16, dt_bias=0.0,
            seed=0, dtype=jnp.float32, a_scale=1.0):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(batch, seq, heads, p), dtype)
    dt = jax.nn.softplus(jnp.asarray(rng.randn(batch, seq, heads) * 0.5
                                     + dt_bias, jnp.float32))
    a = -jnp.exp(jnp.asarray(rng.randn(heads), jnp.float32)) * a_scale
    b, c = (jnp.asarray(rng.randn(batch, seq, groups, n) * 0.5, dtype)
            for _ in range(2))
    d = jnp.asarray(rng.randn(heads), jnp.float32)
    return x, dt, a, b, c, d


@pytest.mark.parametrize("seq,chunk,heads,groups,dt_bias", [
    (64, 32, 4, 2, 0.0), (96, 16, 4, 1, 0.0), (80, 32, 6, 3, -4.6),
    (128, 16, 4, 4, -4.6)],
    ids=["two_chunks", "six_chunks_one_group", "padded_slow",
         "eight_chunks_slow"])
def test_chunked_scan_matches_the_recurrence_values_and_gradients(
        seq, chunk, heads, groups, dt_bias):
    """float32: the value to 1e-5 of its largest, every gradient (x,
    dt, a, b, c, d) to 1e-4 of its largest.  With dt_bias -4.6 (dt
    about 0.01) a head's state decays by e^-0.02 or less a token: it
    carries across every chunk, and the across-chunk product decides
    most of the value."""
    args = _inputs(seq=seq, heads=heads, groups=groups, dt_bias=dt_bias)
    got, got_vjp = jax.vjp(lambda *v: _ssd(*v, chunk=chunk), *args)
    want, want_vjp = jax.vjp(jax.jit(_recurrence), *args)
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) < 1e-5 * scale
    cotangent = jnp.asarray(np.random.RandomState(1).randn(*want.shape),
                            jnp.float32)
    for name, g, w in zip("x dt a b c d".split(), got_vjp(cotangent),
                          want_vjp(cotangent)):
        assert g.shape == w.shape, name
        assert float(jnp.abs(g - w).max()) < 1e-4 * float(
            jnp.abs(w).max()), name
    if dt_bias < 0:
        # the state crosses the chunks: the tokens after the first chunk
        # read far off without what it left (no skip, which hides it)
        x, dt, a, b, c, d = args
        d = jnp.zeros_like(d)
        whole = _ssd(x, dt, a, b, c, d, chunk=chunk)[:, chunk:]
        alone = _ssd(x[:, chunk:], dt[:, chunk:], a, b[:, chunk:],
                       c[:, chunk:], d, chunk=chunk)
        assert float(jnp.abs(whole - alone).max()) > 0.3 * float(
            jnp.abs(whole).max())


def test_the_skip_is_d_times_x():
    x, dt, a, b, c, d = _inputs(seq=64)
    without = _ssd(x, dt, a, b, c, jnp.zeros_like(d), chunk=32)
    np.testing.assert_allclose(
        np.asarray(_ssd(x, dt, a, b, c, d, chunk=32) - without),
        np.asarray(d[:, None] * x), rtol=1e-5, atol=1e-5)


def test_fast_decay_overflows_nothing():
    """dt of 50 on decays of e^3: every exponent is a difference <= 0,
    and nothing overflows."""
    x, dt, a, b, c, d = _inputs(seq=64, dt_bias=50.0)
    a = a * 20.0
    y, vjp = jax.vjp(lambda *v: _ssd(*v, chunk=32), x, dt, a, b, c, d)
    assert bool(jnp.isfinite(y).all())
    assert all(bool(jnp.isfinite(g).all()) for g in vjp(jnp.ones_like(y)))
    want = jax.jit(_recurrence)(x, dt, a, b, c, d)
    assert float(jnp.abs(y - want).max()) < 1e-4 * float(jnp.abs(want).max())


def test_bfloat16_scan_stays_near_the_float32_recurrence():
    """bf16 operands, float32 decays, states and sums: 2 % of the
    largest value."""
    args = _inputs(seq=128, dt_bias=-2.0, dtype=jnp.bfloat16)
    got = _ssd(*args, chunk=32)
    assert got.dtype == jnp.bfloat16
    want = jax.jit(_recurrence)(*[v.astype(jnp.float32) for v in args])
    assert float(jnp.abs(got.astype(jnp.float32) - want).max()) < 2e-2 * \
        float(jnp.abs(want).max())


def test_heads_must_be_a_multiple_of_the_groups():
    from mxnet_tpu.ops.ssm import _k_ssd

    x, dt, a, b, c, d = _inputs(heads=6, groups=4)
    with pytest.raises(ValueError, match="6 heads over 4 groups"):
        _k_ssd(x, dt, a, b, c, d)


def test_the_output_is_named_for_remat_and_the_scan_is_one_checkpoint():
    from mxnet_tpu.ops import registry, ssm
    from mxnet_tpu.parallel import data_parallel

    assert ssm.RESIDUAL_NAMES == registry.RESIDUAL_NAMES["ssd"] \
        == ("ssd_out",)
    assert "ssd_out" in data_parallel._remat_saves()
    text = str(jax.make_jaxpr(lambda *v: ssm._k_ssd(*v, chunk=32))(
        *_inputs(seq=64)))
    assert "name=ssd_out" in text and "remat2[" in text
    assert "while[" not in text and "scan[" not in text


def test_state_space_section_is_on_metrics():
    from mxnet_tpu import profiler
    from mxnet_tpu.ops import ssm
    from mxnet_tpu.telemetry import metrics

    assert "stateSpace" in profiler.section_names()
    profiler.sections(reset=True)
    assert profiler.sections()["stateSpace"]["layers"] == 0
    args = _inputs(seq=80)
    jax.eval_shape(lambda *v: ssm._k_ssd(*v, chunk=32), *args)
    jax.eval_shape(lambda *v: ssm._k_ssd(*v, chunk=32), *args)
    # a shape the kernels take, once: on the CPU the jnp form runs it,
    # and it counts as a kernel trace (on the TPU the kernels run)
    wide = _inputs(batch=1, seq=128, heads=4, p=64, groups=2, n=128,
                   dtype=jnp.bfloat16)
    jax.eval_shape(lambda *v: ssm._k_ssd(*v, chunk=128), *wide)
    stats = profiler.sections()["stateSpace"]
    key, kernel_key = "b2 h4 s80 p8 g2 n16 float32", \
        "b1 h4 s128 p64 g2 n128 bfloat16"
    assert stats == ssm.state_space_stats()
    assert stats["layers"] == 2
    assert stats["traces"] == {key: 2, kernel_key: 1}
    assert stats["kernel_traces"] == {key: 0, kernel_key: 1}
    assert stats["xla_traces"] == {key: 2, kernel_key: 0}
    assert stats["chunk"][key] == 32 and stats["groups"][key] == 2
    assert stats["chunks_per_sequence"][key] == 3       # 80 padded to 96
    assert stats["state_bytes_kept"][key] == 3 * 2 * 4 * 8 * 16 * 4
    text = metrics.default_registry().render()
    assert 'mxtpu_state_space_state_bytes_kept{key="' + key in text
    table = "\n".join(profiler._section_tables())
    assert "3 chunks of 32, 2 groups" in table
    assert f"{kernel_key}: x1 (1 of shapes the kernels take, 0 in " \
        "jax.numpy)" in table
    assert profiler.sections(reset=True)["stateSpace"] == stats
    assert profiler.sections()["stateSpace"]["layers"] == 0


def _against(got, want, truth, tolerance, name):
    """`got` within `tolerance` of `want` (of its largest); in bf16 no
    farther from `truth` (the float32 form) than `want` is, but for a
    last bit."""
    low = got.dtype != jnp.float32
    got, want, truth = (v.astype(jnp.float32) for v in (got, want, truth))
    scale = float(jnp.abs(want).max())
    assert scale > 0, name
    assert float(jnp.abs(got - want).max()) <= tolerance * scale, name
    if low:
        assert float(jnp.abs(got - truth).max()) <= 1.25 * float(
            jnp.abs(want - truth).max()) + 1e-6 * scale, name


@pytest.mark.parametrize("dtype,p,heads,groups,seq,dt_bias", [
    (jnp.float32, 64, 4, 2, 384, 0.0), (jnp.bfloat16, 64, 8, 2, 256, 0.0),
    (jnp.float32, 128, 4, 2, 256, 0.0), (jnp.bfloat16, 128, 2, 1, 256, 0.0),
    (jnp.float32, 64, 4, 1, 384, -4.6)],
    ids=["f32_heads_of_64", "bf16_two_units_of_two_heads",
         "f32_heads_of_128", "bf16_heads_of_128", "f32_slow_four_heads"])
def test_kernels_match_the_jnp_form_interpreted(interpret_pallas, dtype, p,
                                                heads, groups, seq, dt_bias):
    """The kernel pair (`ssd.scan`) against `_ssd_chunked` over two and
    three chunks of 128 at state size 128, two and four heads a group:
    y and all six gradients (x, dt, a, b, c, d) for a random cotangent.
    float32 agrees to round-off; bf16 to a last bit or two of the
    largest (the kernels sum a cotangent's terms in float32 where JAX
    adds them in bf16), and no farther from the float32 form than the
    `jax.numpy` form is."""
    from mxnet_tpu.ops.pallas import ssd
    from mxnet_tpu.ops.ssm import _ssd_chunked

    args = _inputs(batch=1, seq=seq, heads=heads, p=p, groups=groups,
                   n=128, dt_bias=dt_bias, dtype=dtype)
    assert ssd.admits(args[0], args[3], 128)
    got, got_vjp = jax.vjp(lambda *v: ssd.scan(*v, 128), *args)
    want, want_vjp = jax.vjp(lambda *v: _ssd_chunked(*v, 128), *args)
    truth, truth_vjp = jax.vjp(lambda *v: _ssd_chunked(*v, 128),
                               *[v.astype(jnp.float32) for v in args])
    assert got.shape == want.shape and got.dtype == want.dtype == dtype
    tolerance = 1e-5 if dtype == jnp.float32 else 1.6e-2
    _against(got, want, truth, tolerance, "y")
    cotangent = jnp.asarray(np.random.RandomState(2).randn(*want.shape),
                            dtype)
    for name, g, w, t in zip("x dt a b c d".split(), got_vjp(cotangent),
                             want_vjp(cotangent),
                             truth_vjp(cotangent.astype(jnp.float32))):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        _against(g, w, t, 10 * tolerance if name != "d" else tolerance,
                 "d" + name)


def _on_the_tpu_branch(monkeypatch):
    """`ssd` as the TPU lowers it: every `lax.platform_dependent` takes
    its TPU branch; returns the list of `ssd.scan` calls."""
    from mxnet_tpu.ops.pallas import ssd

    monkeypatch.setattr(jax.lax, "platform_dependent",
                        lambda *args, tpu, default: tpu(*args))
    calls, scan = [], ssd.scan
    monkeypatch.setattr(ssd, "scan", lambda *v: calls.append(1) or scan(*v))
    return calls


@pytest.mark.parametrize("dt_bias,a_scale", [(-4.6, 1.0), (50.0, 20.0)],
                         ids=["slow_padded", "fast_decay"])
def test_ssd_with_the_kernels_matches_the_recurrence(
        monkeypatch, interpret_pallas, dt_bias, a_scale):
    """`ssd` with its TPU branch (the kernels interpreted) against the
    token-by-token recurrence, values and all six gradients, float32:
    200 tokens padded to two chunks of 128, two heads of 64 a group.
    Slow decays carry the state across the chunk; a dt of 50 on decays
    of up to e^3 x 20 overflows nothing (every exponent a difference
    <= 0)."""
    from mxnet_tpu.ops.ssm import _k_ssd

    calls = _on_the_tpu_branch(monkeypatch)
    args = _inputs(batch=1, seq=200, heads=4, p=64, groups=2, n=128,
                   dt_bias=dt_bias, a_scale=a_scale)
    got, got_vjp = jax.vjp(lambda *v: _k_ssd(*v, chunk=128), *args)
    assert calls
    want, want_vjp = jax.vjp(jax.jit(_recurrence), *args)
    assert bool(jnp.isfinite(got).all())
    assert float(jnp.abs(got - want).max()) < 1e-5 * float(
        jnp.abs(want).max())
    cotangent = jnp.asarray(np.random.RandomState(3).randn(*want.shape),
                            jnp.float32)
    for name, g, w in zip("x dt a b c d".split(), got_vjp(cotangent),
                          want_vjp(cotangent)):
        assert g.shape == w.shape and bool(jnp.isfinite(g).all()), name
        # (a's gradient is 0 where every decay underflows)
        assert float(jnp.abs(g - w).max()) <= 1e-4 * float(
            jnp.abs(w).max()), name


def test_kernels_take_whole_lanes_only():
    """`ssd.admits`: heads of 64 two to a unit or of a multiple of 128,
    a group's heads on whole lanes, a state of whole lanes, chunks of
    128, bf16 or float32 with B of x's dtype, blocks that fit VMEM."""
    from mxnet_tpu.ops.pallas import ssd

    def admits(heads=64, p=64, groups=8, n=128, chunk=128,
               dtype=jnp.bfloat16, b_dtype=None):
        x = jax.ShapeDtypeStruct((1, 256, heads, p), dtype)
        b = jax.ShapeDtypeStruct((1, 256, groups, n), b_dtype or dtype)
        return ssd.admits(x, b, chunk)

    assert admits() and admits(dtype=jnp.float32) and admits(p=128)
    assert admits(p=256, heads=16) and admits(groups=1)
    assert not admits(p=32) and not admits(p=96) and not admits(n=64)
    assert not admits(heads=8, groups=8)        # one head of 64 a group
    assert not admits(chunk=64) and not admits(chunk=256)
    assert not admits(dtype=jnp.float16)
    assert not admits(b_dtype=jnp.float32)
    assert not admits(heads=64, p=512, groups=1)    # no room in VMEM


def test_shapes_the_kernels_refuse_take_the_jnp_form(monkeypatch):
    """On the TPU branch too, `ssd` sends the shapes `admits` refuses
    to the `jax.numpy` form."""
    from mxnet_tpu.ops.ssm import _k_ssd

    calls = _on_the_tpu_branch(monkeypatch)
    args = _inputs(seq=64)
    got = _k_ssd(*args, chunk=32)
    assert not calls
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(jax.jit(_recurrence)(*args)),
                               rtol=1e-4, atol=1e-4)
