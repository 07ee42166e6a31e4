"""Flash/ring attention tests: pallas kernel (interpret mode on CPU) and
ring SP vs the XLA reference oracle."""
import numpy as np
import pytest

import mxnet_tpu as mx


def _qkv(b=2, h=2, s=256, d=128, seed=0):
    import jax.numpy as jnp

    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.rand(b, h, s, d) * 0.5, jnp.float32)
    return mk(), mk(), mk()


def test_flash_kernel_interpret_matches_reference():
    """Run the pallas kernel in interpreter mode (no TPU needed) and
    compare against the XLA oracle."""
    import functools

    import jax
    from jax.experimental import pallas as pl

    from mxnet_tpu.ops.attention import sdpa_reference
    from mxnet_tpu.ops.pallas import flash_attention as fa

    q, k, v = _qkv(s=256, d=128)
    scale = 1.0 / np.sqrt(q.shape[-1])

    # patch pallas_call into interpret mode for CPU execution
    orig = pl.pallas_call
    try:
        pl.pallas_call = functools.partial(orig, interpret=True)
        out, _ = fa._flash_forward(q, k, v, causal=False, scale=scale)
        out_causal, _ = fa._flash_forward(q, k, v, causal=True,
                                          scale=scale)
    finally:
        pl.pallas_call = orig

    ref = sdpa_reference(q, k, v)
    assert np.allclose(np.asarray(out), np.asarray(ref), atol=2e-3), \
        np.abs(np.asarray(out) - np.asarray(ref)).max()
    ref_causal = sdpa_reference(q, k, v, causal=True)
    assert np.allclose(np.asarray(out_causal), np.asarray(ref_causal),
                       atol=2e-3)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_backward_kernel_matches_reference(causal):
    """The Pallas dQ/dK/dV kernels == XLA-autodiff oracle grads."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from mxnet_tpu.ops.attention import sdpa_reference
    from mxnet_tpu.ops.pallas import flash_attention as fa

    q, k, v = _qkv(s=256, d=128, seed=3)
    scale = 1.0 / np.sqrt(q.shape[-1])
    g = jnp.asarray(np.random.RandomState(4).rand(*q.shape), jnp.float32)

    orig = pl.pallas_call
    try:
        pl.pallas_call = functools.partial(orig, interpret=True)
        out, vjp = jax.vjp(
            lambda q_, k_, v_: fa._flash_sdpa(q_, k_, v_, None, causal,
                                              scale),
            q, k, v)
        dq, dk, dv = vjp(g)
    finally:
        pl.pallas_call = orig

    ref_out, ref_vjp = jax.vjp(
        lambda q_, k_, v_: sdpa_reference(q_, k_, v_, None, scale=scale,
                                          causal=causal), q, k, v)
    rq, rk, rv = ref_vjp(g)
    assert np.allclose(np.asarray(out), np.asarray(ref_out), atol=2e-3)
    for a, b, name in [(dq, rq, "dq"), (dk, rk, "dk"), (dv, rv, "dv")]:
        assert np.allclose(np.asarray(a), np.asarray(b), atol=5e-3), \
            (name, np.abs(np.asarray(a) - np.asarray(b)).max())


def test_flash_attention_fallback_unaligned():
    """Unaligned shapes take the XLA fallback silently."""
    from mxnet_tpu.ops.attention import _k_sdpa, sdpa_reference

    q, k, v = _qkv(s=40, d=16)
    out = _k_sdpa(q, k, v, None, scale=None, causal=False)
    ref = sdpa_reference(q, k, v)
    assert np.allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_ring_attention_matches_reference():
    """Ring attention over an 8-device sp axis == single-device oracle."""
    from mxnet_tpu.ops.attention import sdpa_reference
    from mxnet_tpu.parallel.ring_attention import ring_attention

    q, k, v = _qkv(b=1, h=2, s=64, d=16)
    out = ring_attention(q, k, v)
    ref = sdpa_reference(q, k, v)
    assert np.allclose(np.asarray(out), np.asarray(ref), atol=1e-4), \
        np.abs(np.asarray(out) - np.asarray(ref)).max()


def test_ring_attention_causal():
    from mxnet_tpu.ops.attention import sdpa_reference
    from mxnet_tpu.parallel.ring_attention import ring_attention

    q, k, v = _qkv(b=1, h=1, s=64, d=16, seed=3)
    out = ring_attention(q, k, v, causal=True)
    ref = sdpa_reference(q, k, v, causal=True)
    assert np.allclose(np.asarray(out), np.asarray(ref), atol=1e-4), \
        np.abs(np.asarray(out) - np.asarray(ref)).max()


def test_ring_attention_grad_flows():
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.parallel.ring_attention import ring_attention
    from mxnet_tpu.ops.attention import sdpa_reference
    from mxnet_tpu.parallel import mesh as mesh_mod

    q, k, v = _qkv(b=1, h=1, s=32, d=16, seed=5)

    def loss_ring(q_, k_, v_):
        return jnp.sum(ring_attention(q_, k_, v_) ** 2)

    def loss_ref(q_, k_, v_):
        return jnp.sum(sdpa_reference(q_, k_, v_) ** 2)

    g_ring = jax.grad(loss_ring)(q, k, v)
    g_ref = jax.grad(loss_ref)(q, k, v)
    assert np.allclose(np.asarray(g_ring), np.asarray(g_ref), atol=1e-3)


# -- Ulysses all-to-all sequence parallelism --------------------------------


def test_ulysses_attention_matches_reference():
    """All-to-all SP over 8 devices == single-device oracle."""
    from mxnet_tpu.ops.attention import sdpa_reference
    from mxnet_tpu.parallel.ulysses import ulysses_attention

    q, k, v = _qkv(b=2, h=8, s=64, d=16, seed=7)
    out = ulysses_attention(q, k, v)
    ref = sdpa_reference(q, k, v)
    assert np.allclose(np.asarray(out), np.asarray(ref), atol=1e-4), \
        np.abs(np.asarray(out) - np.asarray(ref)).max()


def test_ulysses_attention_causal_exact():
    """Each device holds the FULL sequence for its heads, so causal
    masking is exact (no online-softmax recurrence)."""
    from mxnet_tpu.ops.attention import sdpa_reference
    from mxnet_tpu.parallel.ulysses import ulysses_attention

    q, k, v = _qkv(b=1, h=8, s=64, d=16, seed=9)
    out = ulysses_attention(q, k, v, causal=True)
    ref = sdpa_reference(q, k, v, causal=True)
    assert np.allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_ulysses_attention_grad_flows():
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.attention import sdpa_reference
    from mxnet_tpu.parallel.ulysses import ulysses_attention

    q, k, v = _qkv(b=1, h=8, s=32, d=16, seed=11)

    def loss_u(q_, k_, v_):
        return jnp.sum(ulysses_attention(q_, k_, v_) ** 2)

    def loss_ref(q_, k_, v_):
        return jnp.sum(sdpa_reference(q_, k_, v_) ** 2)

    g_u = jax.grad(loss_u)(q, k, v)
    g_ref = jax.grad(loss_ref)(q, k, v)
    assert np.allclose(np.asarray(g_u), np.asarray(g_ref), atol=1e-3)


def test_ulysses_rejects_indivisible_heads():
    import pytest

    import mxnet_tpu as mx
    from mxnet_tpu.parallel.ulysses import ulysses_attention

    q, k, v = _qkv(b=1, h=3, s=64, d=16)  # 3 heads, 8 devices
    with pytest.raises(mx.MXNetError, match="heads"):
        ulysses_attention(q, k, v)


def test_flash_kernel_head_dim_64():
    """head_dim=64 (BERT/GPT heads) must use the Pallas path, fwd+bwd
    (previously fell back to XLA because of a d%128 gate)."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from mxnet_tpu.ops.attention import sdpa_reference
    from mxnet_tpu.ops.pallas import flash_attention as fa

    q, k, v = _qkv(s=256, d=64)
    assert fa._tiles_ok(q, k)  # no longer gated out

    scale = 1.0 / np.sqrt(q.shape[-1])
    orig = pl.pallas_call
    try:
        pl.pallas_call = functools.partial(orig, interpret=True)
        out, lse = fa._flash_forward(q, k, v, causal=True, scale=scale)
        # backward through the pallas kernels
        g = jnp.ones_like(out)
        dq, dk, dv = fa._flash_backward(q, k, v, out, lse, g,
                                        causal=True, scale=scale)
    finally:
        pl.pallas_call = orig

    ref = sdpa_reference(q, k, v, causal=True)
    assert np.allclose(np.asarray(out), np.asarray(ref), atol=2e-3)

    def ref_loss(q, k, v):
        return jnp.sum(sdpa_reference(q, k, v, causal=True))

    rdq, rdk, rdv = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for got, want, name in ((dq, rdq, "dq"), (dk, rdk, "dk"),
                            (dv, rdv, "dv")):
        err = np.abs(np.asarray(got) - np.asarray(want)).max()
        assert err < 5e-3, (name, err)


def test_flash_kernel_key_padding_mask():
    """The (b,1,1,sk) additive key-padding mask (BERT's form) rides the
    Pallas kernels fwd+bwd; full-score masks still fall back."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from mxnet_tpu.ops.attention import sdpa_reference
    from mxnet_tpu.ops.pallas import flash_attention as fa

    b, h, s, d = 2, 2, 256, 64
    q, k, v = _qkv(b=b, h=h, s=s, d=d)
    # pad out the tail third of keys per batch row
    valid = np.array([s, s - 96], np.int32)
    add = np.zeros((b, 1, 1, s), np.float32)
    for i in range(b):
        add[i, 0, 0, valid[i]:] = -1e9
    add = jnp.asarray(add)

    km = fa._as_key_padding_mask(add, q, k)
    assert km is not None and km.shape == (b, s)
    # bool masks normalize too
    bmask = jnp.asarray(add == 0)
    np.testing.assert_allclose(
        np.asarray(fa._as_key_padding_mask(bmask, q, k) < -1e8),
        np.asarray(add < -1e8).reshape(b, s))
    # a full (sq, sk) score mask is NOT a key-padding mask
    assert fa._as_key_padding_mask(
        jnp.zeros((b, 1, s, s), jnp.float32), q, k) is None

    scale = 1.0 / np.sqrt(d)
    orig = pl.pallas_call
    try:
        pl.pallas_call = functools.partial(orig, interpret=True)
        out, lse = fa._flash_forward(q, k, v, causal=False, scale=scale,
                                     kmask=km)
        g = jnp.ones_like(out)
        dq, dk, dv = fa._flash_backward(q, k, v, out, lse, g,
                                        causal=False, scale=scale,
                                        kmask=km)
    finally:
        pl.pallas_call = orig

    ref = sdpa_reference(q, k, v, add)
    assert np.allclose(np.asarray(out), np.asarray(ref), atol=2e-3), \
        np.abs(np.asarray(out) - np.asarray(ref)).max()

    def ref_loss(q, k, v):
        return jnp.sum(sdpa_reference(q, k, v, add))

    rdq, rdk, rdv = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for got, want, name in ((dq, rdq, "dq"), (dk, rdk, "dk"),
                            (dv, rdv, "dv")):
        err = np.abs(np.asarray(got) - np.asarray(want)).max()
        assert err < 5e-3, (name, err)


def test_flash_kernel_causal_plus_padding_mask():
    """Causal early-exit loop bounds must compose with the key-padding
    mask (a decoder over padded batches) — fwd and bwd."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from mxnet_tpu.ops.attention import sdpa_reference
    from mxnet_tpu.ops.pallas import flash_attention as fa

    b, h, s, d = 2, 2, 256, 64
    q, k, v = _qkv(b=b, h=h, s=s, d=d, seed=5)
    add = np.zeros((b, 1, 1, s), np.float32)
    add[0, 0, 0, 200:] = -1e9
    add = jnp.asarray(add)
    km = fa._as_key_padding_mask(add, q, k)
    scale = 1.0 / np.sqrt(d)

    orig = pl.pallas_call
    try:
        pl.pallas_call = functools.partial(orig, interpret=True)
        out, lse = fa._flash_forward(q, k, v, causal=True, scale=scale,
                                     kmask=km)
        g = jnp.ones_like(out)
        dq, dk, dv = fa._flash_backward(q, k, v, out, lse, g,
                                        causal=True, scale=scale,
                                        kmask=km)
    finally:
        pl.pallas_call = orig

    ref = sdpa_reference(q, k, v, add, causal=True)
    assert np.allclose(np.asarray(out), np.asarray(ref), atol=2e-3)

    def ref_loss(q, k, v):
        return jnp.sum(sdpa_reference(q, k, v, add, causal=True))

    rdq, rdk, rdv = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for got, want, name in ((dq, rdq, "dq"), (dk, rdk, "dk"),
                            (dv, rdv, "dv")):
        err = np.abs(np.asarray(got) - np.asarray(want)).max()
        assert err < 5e-3, (name, err)


@pytest.mark.parametrize("causal,masked", [(False, False), (True, False),
                                           (False, True)])
def test_flash_streamed_matches_reference(causal, masked,
                                          interpret_pallas, monkeypatch):
    """Streamed flash attention (K/V swept by a grid dim, the long-KV
    path past the VMEM bound): forward AND all three grads must match
    the XLA oracle exactly. The tiny threshold forces streaming at
    test sizes."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.attention import sdpa_reference
    from mxnet_tpu.ops.pallas import flash_attention as fa

    monkeypatch.setenv("MXTPU_FLASH_MAX_KV_VMEM_MB", "0.0001")
    rng = np.random.RandomState(0)
    b, h, d = 2, 2, 64
    sq, sk = (256, 256) if causal else (256, 384)
    q = jnp.asarray(rng.randn(b, h, sq, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, h, sk, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, h, sk, d), jnp.float32)
    km = None
    mask4 = None
    if masked:
        km = jnp.asarray(
            np.where(rng.rand(b, sk) > 0.2, 0.0, -1e9), jnp.float32)
        mask4 = km.reshape(b, 1, 1, sk)

    assert not fa._kv_resident(q, k)  # threshold forces the stream path
    out = fa._flash_sdpa(q, k, v, km, causal, 0.125)
    ref = sdpa_reference(q, k, v, mask4, scale=0.125, causal=causal)
    assert np.allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    gf = jax.grad(lambda a, bb, c: (
        fa._flash_sdpa(a, bb, c, km, causal, 0.125) ** 2).sum(),
        argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda a, bb, c: (
        sdpa_reference(a, bb, c, mask4, scale=0.125,
                       causal=causal) ** 2).sum(),
        argnums=(0, 1, 2))(q, k, v)
    for a, r in zip(gf, gr):
        denom = np.abs(np.asarray(r)).max() + 1e-9
        assert np.abs(np.asarray(a) - np.asarray(r)).max() / denom < 2e-5


def test_flash_causal_cross_length_uses_oracle():
    """causal with sq != sk is END-aligned in the reference (tril
    offset); the kernels are start-aligned, so the public op must
    route cross-length causal to the oracle."""
    from mxnet_tpu.ops.attention import sdpa_reference
    from mxnet_tpu.ops.pallas.flash_attention import flash_attention

    rng = np.random.RandomState(1)
    import jax.numpy as jnp

    q = jnp.asarray(rng.randn(1, 2, 128, 64), jnp.float32)
    k = jnp.asarray(rng.randn(1, 2, 256, 64), jnp.float32)
    out = flash_attention(q, k, k, causal=True)
    ref = sdpa_reference(q, k, k, causal=True)
    assert np.allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


# ---------------------------------------------------------------------------
# grouped resident kernels: one grid step works on G heads of a batch row
# ---------------------------------------------------------------------------


def _grouped_case(b, h, sq, sk, d, dtype, masked, seed=0):
    """q, k, v, the (b, 1, 1, sk) additive key-padding mask (another
    length in every batch row, so a group that took a neighbour's row
    would show) and a weight for the loss that gives every output
    element a gradient of its own."""
    import jax.numpy as jnp

    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(b, h, sq, d) * 0.5, dtype)
    k = jnp.asarray(rng.randn(b, h, sk, d) * 0.5, dtype)
    v = jnp.asarray(rng.randn(b, h, sk, d) * 0.5, dtype)
    mask = None
    if masked:
        valid = sk - 32 * (1 + np.arange(b))
        mask = jnp.asarray(np.where(
            np.arange(sk)[None] < valid[:, None], 0.0, -1e9),
            jnp.float32).reshape(b, 1, 1, sk)
    w = jnp.asarray(rng.randn(b, h, sq, d), jnp.float32)
    return q, k, v, mask, w


def _out_and_grads(fn, q, k, v, mask, w, causal):
    import jax
    import jax.numpy as jnp

    def loss(q, k, v):
        out = fn(q, k, v, mask, causal=causal)
        return (out.astype(jnp.float32) * w).sum(), out

    (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                         has_aux=True)(q, k, v)
    return (out,) + grads


def _with_heads_per_step(monkeypatch, heads):
    from mxnet_tpu.ops.pallas import flash_attention as fa

    monkeypatch.setattr(fa, "_heads_per_step",
                        lambda h, *a, **kw: h if heads == "all" else heads)
    fa.reset_flash_attention_stats()
    return fa


def _assert_close_to_oracle(got, q, k, v, mask, w, causal):
    """Forward and the three gradients against `sdpa_reference` in
    float32 on the same (already rounded) operands."""
    import jax.numpy as jnp

    from mxnet_tpu.ops.attention import sdpa_reference

    f32 = [t.astype(jnp.float32) for t in (q, k, v)]
    want = _out_and_grads(sdpa_reference, *f32, mask, w, causal)
    tol = 2e-5 if q.dtype == jnp.float32 else 2e-2
    for name, a, r in zip(("out", "dq", "dk", "dv"), got, want):
        a = np.asarray(a.astype(jnp.float32))
        r = np.asarray(r)
        err = np.abs(a - r).max() / (np.abs(r).max() + 1e-9)
        assert err < tol, (name, err)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("masked", [False, True], ids=["nomask", "padmask"])
@pytest.mark.parametrize("heads", [1, 2, 4, "all"])
def test_flash_grouped_heads_match_reference(heads, masked, causal, dtype,
                                             interpret_pallas, monkeypatch):
    """Whatever the heads a grid step (1, 2, 4 or all 8 of a batch
    row), forward and backward agree with the oracle: two batch rows
    with a key-padding mask of another length each, two q- and k-blocks
    (the causal loop bounds), both dtypes."""
    fa = _with_heads_per_step(monkeypatch, heads)
    b, h, s, d = 2, 8, 256, 64
    q, k, v, mask, w = _grouped_case(b, h, s, s, d, dtype, masked)
    got = _out_and_grads(fa.flash_attention, q, k, v, mask, w, causal)
    _assert_close_to_oracle(got, q, k, v, mask, w, causal)
    want_heads = h if heads == "all" else heads
    assert {key[4] for key in fa._built} == {want_heads}
    assert {key[0] for key in fa._built} == {"resident"}


@pytest.mark.parametrize("heads", [1, 3, "all"])
def test_flash_grouped_heads_cross_length(heads, interpret_pallas,
                                          monkeypatch):
    """sq != sk, not causal, three q-blocks against two k-blocks, a
    group size that is not a power of two."""
    fa = _with_heads_per_step(monkeypatch, heads)
    q, k, v, mask, w = _grouped_case(2, 6, 384, 256, 64, "float32", True)
    got = _out_and_grads(fa.flash_attention, q, k, v, mask, w, False)
    _assert_close_to_oracle(got, q, k, v, mask, w, False)
    assert {key[2] for key in fa._built} == {(2, 6, 384, 256, 64)}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_flash_one_head_a_step_equals_all_heads_a_step(
        dtype, interpret_pallas, monkeypatch):
    """The group is a matter of blocks, not of arithmetic: a head a
    step and all heads a step give the same numbers.  To 1e-6 of the
    largest element, not to the bit: the CPU's compiled interpreter may
    fuse the unrolled heads' float32 sums in another order."""
    import jax.numpy as jnp

    q, k, v, mask, w = _grouped_case(2, 4, 256, 256, 64, dtype, True,
                                     seed=3)
    runs = []
    for heads in (1, "all"):
        fa = _with_heads_per_step(monkeypatch, heads)
        runs.append(_out_and_grads(fa.flash_attention, q, k, v, mask, w,
                                   True))
    for name, one, every in zip(("out", "dq", "dk", "dv"), *runs):
        one = np.asarray(one.astype(jnp.float32))
        every = np.asarray(every.astype(jnp.float32))
        assert np.abs(one - every).max() <= 1e-6 * np.abs(one).max(), name


def test_heads_per_step_rule():
    """The heads a grid step come from the operands' shapes and item
    size alone: a divisor of h (a group never straddles two batch
    rows), all 12 at BERT-base's shape, fewer as the sequence grows,
    one where a single head's K/V fill the budget; the batch is not an
    argument at all."""
    import inspect

    from mxnet_tpu.ops.pallas import flash_attention as fa

    rule = fa._heads_per_step
    assert "b" not in inspect.signature(rule).parameters
    assert rule(12, 128, 128, 64, 2) == 12          # bert_base.seq128
    assert rule(12, 512, 512, 64, 2) == 6           # bert_base.seq512
    assert rule(12, 128, 128, 64, 4) == 6           # the fp32 whole step
    assert rule(16, 2048, 2048, 128, 2) == 1
    assert rule(1, 16384, 16384, 128, 2) == 1
    assert rule(7, 128, 128, 64, 2) == 7            # a prime head count
    assert rule(7, 1024, 1024, 64, 2) == 1
    for h in (1, 2, 6, 8, 12, 16, 40, 96):
        for s in (128, 256, 1024, 4096):
            for d in (64, 128, 192):
                for itemsize in (2, 4):
                    g = rule(h, s, s, d, itemsize)
                    assert h % g == 0 and 1 <= g <= 128
                    blocks = 2 * itemsize * d * (4 * 128 + 3 * s) * g
                    assert g == 1 or blocks <= fa._GROUP_VMEM_BYTES
    # the longer of the two sequences decides (the dK/dV kernel keeps q
    # resident, the dQ kernel k and v)
    assert rule(12, 128, 2048, 64, 2) == rule(12, 2048, 128, 64, 2) \
        == rule(12, 2048, 2048, 64, 2)
    # a group's blocks are cut from the (b*h, s, d) operand by a
    # BlockSpec: b*h is a multiple of the group for every batch
    import jax.numpy as jnp

    q = jnp.zeros((5, 12, 128, 64), jnp.bfloat16)
    assert (q.shape[0] * q.shape[1]) % rule(12, 128, 128, 64, 2) == 0


_NOTHING_NAMED = {"residuals_named": 0, "residual_pairs": {},
                  "residual_bytes": {}}


def test_flash_attention_profiler_section(interpret_pallas):
    """The `flashAttention` section: every kernel built while a program
    is TRACED is counted once, with its variant, shapes, heads a grid
    step and grid; running the traced program again counts nothing;
    the section is in the profiler's dump, its table and /metrics, and
    a reset dump clears it."""
    import json

    import jax
    import jax.numpy as jnp

    from mxnet_tpu import profiler
    from mxnet_tpu.ops.pallas import flash_attention as fa
    from mxnet_tpu.telemetry import metrics

    assert "flashAttention" in profiler.section_names()
    profiler.sections(reset=True)
    assert profiler.sections()["flashAttention"] == {
        "kernels": 0, "resident": 0, "streamed": 0, "grouped": 0,
        "built": {}, **_NOTHING_NAMED}

    q, k, v, mask, w = _grouped_case(2, 4, 128, 128, 64, "float32", True)
    step = jax.jit(jax.grad(
        lambda q: (fa.flash_attention(q, k, v, mask) * w).sum()))
    step(q)
    step(q + 1.0)     # the same program: nothing is traced, or counted
    rows = {
        f"resident {kernel} b2 h4 sq128 sk128 d64 float32 heads4 grid2x1": 1
        for kernel in ("fwd", "dq", "dkv")}
    # differentiation ran the fwd rule, which named its (out, lse)
    # pair: 2*4*128*64 and 2*4*128 float32
    named = {"residuals_named": 1,
             "residual_pairs": {"resident b2 h4 sq128 sk128 d64 float32": 1},
             "residual_bytes": {
                 "resident b2 h4 sq128 sk128 d64 float32": 4 * 1024 * 65}}
    assert profiler.sections()["flashAttention"] == {
        "kernels": 3, "resident": 3, "streamed": 0, "grouped": 0,
        "built": rows, **named}
    assert fa._heads_per_step(4, 128, 128, 64, 4) == 4

    # a second program is traced (another batch): its kernels are rows
    # of their own; without differentiation no fwd rule runs and
    # nothing is named
    jax.jit(lambda q: fa.flash_attention(q, q, q))(
        jnp.concatenate([q, q]))
    stats = profiler.sections()["flashAttention"]
    assert stats["kernels"] == 4 and stats["built"][
        "resident fwd b4 h4 sq128 sk128 d64 float32 heads4 grid4x1"] == 1
    assert {k: stats[k] for k in named} == named

    assert json.loads(profiler.dumps())["flashAttention"] == stats
    table = "\n".join(profiler._section_tables())
    assert "Flash Attention (kernels built at trace time):" in table
    assert "resident dkv b2 h4 sq128 sk128 d64 float32 heads4 grid2x1  x1" \
        in table
    assert ("  named resident b2 h4 sq128 sk128 d64 float32  x1  "
            "266240 bytes") in table
    text = metrics.default_registry().render()
    assert "mxtpu_flash_attention_kernels 4" in text
    assert "mxtpu_flash_attention_resident 4" in text
    assert ('mxtpu_flash_attention_built{key="resident dq b2 h4 sq128 '
            'sk128 d64 float32 heads4 grid2x1"} 1') in text
    assert "mxtpu_flash_attention_residuals_named 1" in text
    assert ('mxtpu_flash_attention_residual_bytes{key="resident b2 h4 '
            'sq128 sk128 d64 float32"} 266240') in text

    assert json.loads(profiler.dumps(reset=True))[
        "flashAttention"]["kernels"] == 4
    assert profiler.sections()["flashAttention"]["kernels"] == 0


def test_flash_attention_section_counts_streamed(interpret_pallas,
                                                 monkeypatch):
    from mxnet_tpu import profiler
    from mxnet_tpu.ops.pallas import flash_attention as fa

    monkeypatch.setenv("MXTPU_FLASH_MAX_KV_VMEM_MB", "0.0001")
    profiler.sections(reset=True)
    q, k, v, _, _ = _grouped_case(1, 2, 128, 256, 64, "float32", False)
    fa.flash_attention(q, k, v)
    assert profiler.sections()["flashAttention"] == {
        "kernels": 1, "resident": 0, "streamed": 1, "grouped": 0, "built": {
            "streamed fwd b1 h2 sq128 sk256 d64 float32 heads1 "
            "grid2x1x2": 1}, **_NOTHING_NAMED}


@pytest.mark.parametrize("window", [None, 128])
def test_flash_attention_section_counts_named_residuals(window,
                                                        interpret_pallas):
    """A grouped kernel's fwd rule names its output and row statistic
    (`RESIDUAL_NAMES`): the section has the pair and its bytes under
    the variant's shape, its K/V heads and window; a plain forward
    (no fwd rule) names nothing; and under a `jax.checkpoint` whose
    policy saves the names the gradient is the bare checkpoint's."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu import profiler
    from mxnet_tpu.ops.pallas import flash_attention as fa

    q, k, v, w = _shared_kv_case(3, 256, 64)

    def loss(q, k, v):
        out = fa.flash_attention(q, k, v, causal=True, window=window)
        return (out * w).sum()

    jax.clear_caches()
    profiler.sections(reset=True)
    loss(q, k, v)
    assert profiler.sections()["flashAttention"]["residuals_named"] == 0
    policy = jax.checkpoint_policies.save_only_these_names(
        *fa.RESIDUAL_NAMES)
    kept = jax.grad(jax.checkpoint(loss, policy=policy),
                    argnums=(0, 1, 2))(q, k, v)
    row = f"grouped b1 h6 sq256 sk256 d64 kv2 window{window or 0} float32"
    stats = profiler.sections()["flashAttention"]
    assert stats["residuals_named"] == 1
    assert stats["residual_pairs"] == {row: 1}
    assert stats["residual_bytes"] == {row: 4 * 6 * 256 * (64 + 1)}
    bare = jax.grad(jax.checkpoint(loss), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(kept, bare):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- grouped variant: shared K/V heads and / or a sliding window -------------

def _shared_kv_case(group, seq, d, dtype="float32", kv_heads=2, seed=5):
    import jax.numpy as jnp

    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(1, kv_heads * group, seq, d), dtype)
    k = jnp.asarray(rng.randn(1, kv_heads, seq, d), dtype)
    v = jnp.asarray(rng.randn(1, kv_heads, seq, d), dtype)
    w = jnp.asarray(rng.randn(1, kv_heads * group, seq, d), "float32")
    return q, k, v, w


def _windowed_out_and_grads(fn, q, k, v, w, window):
    import jax
    import jax.numpy as jnp

    def loss(q, k, v):
        out = fn(q, k, v, None, causal=True, window=window)
        return (out.astype(jnp.float32) * w).sum(), out

    (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                         has_aux=True)(q, k, v)
    return (out,) + grads


@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("group", [1, 6, 8])
@pytest.mark.parametrize("window", [None, 128, 512])
def test_flash_window_and_shared_kv_heads_match_reference(
        window, group, d, interpret_pallas):
    """Forward and the three gradients of the kernels against
    `sdpa_reference` over window x query heads a K/V head x head size;
    sequence 768 puts whole, edge and unseen k-blocks under every
    window."""
    import jax.numpy as jnp

    from mxnet_tpu.ops.attention import sdpa_reference
    from mxnet_tpu.ops.pallas import flash_attention as fa

    fa.reset_flash_attention_stats()
    q, k, v, w = _shared_kv_case(group, 768, d)
    got = _windowed_out_and_grads(fa.flash_attention, q, k, v, w, window)
    want = _windowed_out_and_grads(sdpa_reference, q, k, v, w, window)
    for name, a, r in zip(("out", "dq", "dk", "dv"), got, want):
        err = float(jnp.abs(a - r).max() / (jnp.abs(r).max() + 1e-9))
        assert err < 2e-5, (name, err)
    stats = fa.flash_attention_stats()
    if window is None and group == 1:
        assert stats["grouped"] == 0 and stats["resident"] == 3
    else:
        assert stats["grouped"] == 3 and stats["resident"] == 0
        assert any(f"kv2 window{window or 0}" in row
                   for row in stats["built"])


def test_flash_shared_kv_heads_wide_k_blocks(interpret_pallas):
    """Without a window the grouped forward walks k-blocks of 512 under
    q-blocks of 128 (sequence a multiple of 512): the diagonal crosses
    a k-block four q-blocks long."""
    import jax.numpy as jnp

    from mxnet_tpu.ops.attention import sdpa_reference
    from mxnet_tpu.ops.pallas import flash_attention as fa

    assert fa._GROUPED_BLOCK_K_FULL == 512
    q, k, v, w = _shared_kv_case(3, 1024, 128, kv_heads=1)
    got = _windowed_out_and_grads(fa.flash_attention, q, k, v, w, None)
    want = _windowed_out_and_grads(sdpa_reference, q, k, v, w, None)
    for name, a, r in zip(("out", "dq", "dk", "dv"), got, want):
        err = float(jnp.abs(a - r).max() / (jnp.abs(r).max() + 1e-9))
        assert err < 2e-5, (name, err)


def test_no_window_and_equal_heads_trace_to_todays_kernels(
        interpret_pallas):
    """`window=None` with as many K/V heads as query heads is the
    resident kernels' program of before, equation for equation."""
    import jax

    from mxnet_tpu.ops.pallas import flash_attention as fa

    q, k, v, _ = _shared_kv_case(1, 256, 64)

    def today(q, k, v):
        return fa._flash_sdpa(q, k, v, None, True, 0.125).sum()

    def now(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, window=None).sum()

    assert str(jax.make_jaxpr(jax.grad(now, argnums=(0, 1, 2)))(q, k, v)) \
        == str(jax.make_jaxpr(jax.grad(today, argnums=(0, 1, 2)))(q, k, v))
    fa.reset_flash_attention_stats()
    jax.make_jaxpr(now)(q, k, v)
    stats = fa.flash_attention_stats()
    assert stats["resident"] == 1 and stats["grouped"] == 0


def test_flash_window_bfloat16_and_op_dispatch(interpret_pallas,
                                               monkeypatch):
    """bf16 operands (the chip's): products in bf16, statistics and
    accumulation in float32.  And the registry op: `window` reaches the
    XLA form on the CPU, K/V heads are read from the shapes."""
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu.ops.attention import sdpa_reference
    from mxnet_tpu.ops.pallas import flash_attention as fa

    q, k, v, w = _shared_kv_case(4, 384, 128, "bfloat16")
    got = _windowed_out_and_grads(fa.flash_attention, q, k, v, w, 200)
    f32 = [t.astype(jnp.float32) for t in (q, k, v)]
    want = _windowed_out_and_grads(sdpa_reference, *f32, w, 200)
    for name, a, r in zip(("out", "dq", "dk", "dv"), got, want):
        err = float(jnp.abs(a.astype(jnp.float32) - r).max()
                    / (jnp.abs(r).max() + 1e-9))
        assert err < 2e-2, (name, err)

    out = mx.nd.scaled_dot_product_attention(
        mx.nd.array(np.asarray(f32[0])), mx.nd.array(np.asarray(f32[1])),
        mx.nd.array(np.asarray(f32[2])), causal=True, window=200)
    np.testing.assert_allclose(out.asnumpy(), np.asarray(want[0]),
                               rtol=2e-5, atol=2e-5)
    with pytest.raises(ValueError, match="window needs causal"):
        sdpa_reference(*f32, window=8)
    with pytest.raises(ValueError, match="window needs causal"):
        fa.flash_attention(q, k, v, window=8)


def test_sdpa_reference_window_and_groups_by_hand():
    """The oracle itself, against the definition: position i sees j
    with 0 <= i - j < window; query head j reads K/V head j // group."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.attention import sdpa_reference

    q, k, v, _ = _shared_kv_case(3, 16, 8)
    got = sdpa_reference(q, k, v, causal=True, window=5)
    i, j = np.arange(16)[:, None], np.arange(16)[None, :]
    seen = (j <= i) & (i - j < 5)
    for head in range(6):
        logits = np.asarray(q[0, head] @ k[0, head // 3].T) / np.sqrt(8)
        probs = jax.nn.softmax(jnp.where(seen, logits, -np.inf), axis=-1)
        np.testing.assert_allclose(np.asarray(got[0, head]),
                                   np.asarray(probs @ v[0, head // 3]),
                                   rtol=1e-5, atol=1e-5)


def test_grouped_gate_falls_to_the_xla_form_outside_its_shapes(
        interpret_pallas):
    from mxnet_tpu.ops.pallas import flash_attention as fa

    fa.reset_flash_attention_stats()
    q, k, v, _ = _shared_kv_case(2, 100, 64)       # 100 does not tile
    fa.flash_attention(q, k, v, causal=True, window=16)
    assert fa.flash_attention_stats()["kernels"] == 0
