"""The decoder LM (models/decoder_lm.py), its expert layer (ops/moe.py)
and its small ops against the plain reference of the benchmark
(benchmarks/reference/laguna_xs2.py, float32 jax.numpy, no mxnet_tpu),
at a small size on the CPU with seeded weights: loss and every leaf's
gradient through `DataParallelTrainer.step`, both layer kinds and both
rotary forms; no assignment dropped whatever the imbalance; and the
shares of an expert-parallel layer adding up to the uncut layer.  The
hybrid model (Gated DeltaNet layers beside a gated attention layer,
a gated shared expert) against benchmarks/reference/qwen3_next_80b.py,
whose delta rule runs token by token."""
from __future__ import annotations

import copy
import importlib.util
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")

SMALL = dict(
    vocab_size=128, hidden_size=64, head_dim=16, num_key_value_heads=2,
    intermediate_size=128, moe_intermediate_size=32,
    shared_expert_intermediate_size=32, num_experts=16, router_width=16,
    first_expert=0, num_experts_per_tok=2, moe_routed_scaling_factor=2.5,
    rms_norm_eps=1e-6, sliding_window=8, num_hidden_layers=3,
    layer_types=["full_attention", "sliding_attention", "full_attention"],
    num_attention_heads_per_layer=[12, 16, 12],
    mlp_layer_types=["dense", "sparse", "sparse"],
    rope_parameters={
        "full_attention": dict(
            rope_theta=500000, rope_type="yarn", factor=64,
            original_max_position_embeddings=16, beta_slow=1, beta_fast=64,
            attention_factor=1.4158883083359672, partial_rotary_factor=0.5),
        "sliding_attention": dict(rope_type="default", rope_theta=10000,
                                  partial_rotary_factor=1)},
    assumed={"init_stdev": 0.05,
             "optimizer": {"name": "adamw", "learning_rate": 1e-3,
                           "wd": 0.01, "beta1": 0.9, "beta2": 0.999,
                           "epsilon": 1e-8}})


# the hybrid model at a small size, in the PUBLISHED key names of
# Qwen3-Next's config.json: 3 Gated DeltaNet layers to 1 gated
# attention layer, an expert layer with a gated shared expert in each
SMALL_HYBRID = dict(
    vocab_size=128, hidden_size=64, head_dim=16, num_attention_heads=8,
    num_key_value_heads=2, linear_num_key_heads=2, linear_num_value_heads=4,
    linear_key_head_dim=8, linear_value_head_dim=16,
    linear_conv_kernel_dim=4, full_attention_interval=4,
    decoder_sparse_step=1, mlp_only_layers=[], num_hidden_layers=4,
    moe_intermediate_size=32, shared_expert_intermediate_size=32,
    num_experts=16, router_width=16, first_expert=0, num_experts_per_tok=3,
    rms_norm_eps=1e-6, rope_theta=10000000, partial_rotary_factor=0.25,
    assumed={"init_stdev": 0.05, "conv_init_stdev": 0.25,
             "optimizer": {"name": "adamw", "learning_rate": 1e-3,
                           "wd": 0.01, "beta1": 0.9, "beta2": 0.999,
                           "epsilon": 1e-8}})


def _bench_module(directory, name):
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    spec = importlib.util.spec_from_file_location(
        f"{directory}_{name}", os.path.join(BENCH, directory, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def reference():
    return _bench_module("reference", "laguna_xs2")


@pytest.fixture(scope="module")
def hybrid():
    """(the plain reference, the function that turns the published
    keys into `DecoderLM`'s config) of the hybrid model."""
    return (_bench_module("reference", "qwen3_next_80b"),
            _bench_module("configs", "qwen3_next_80b").decoder_config)


def _weights(reference, config, seed=3):
    from harness import weights

    return weights.make(seed, reference.param_specs(config))


def _batch(config, rows=4, seq=32, seed=0):
    tokens = np.random.RandomState(seed).randint(
        0, config["vocab_size"], (rows, seq + 1)).astype(np.int32)
    return tokens[:, :-1], tokens[:, 1:]


def _one_device_mesh():
    """The cell's mesh: one chip (the suite has 8 virtual devices)."""
    import jax

    from mxnet_tpu.parallel import mesh

    return mesh.make_mesh(devices=jax.devices()[:1])


def _filled(config, arrays):
    import mxnet_tpu as mx
    from mxnet_tpu.models import decoder_lm
    from mxnet_tpu.ndarray.ndarray import NDArray

    net = decoder_lm.DecoderLM(config)
    net.initialize(mx.init.Zero())
    for (_, p), a in zip(net._ordered_params(), arrays):
        assert tuple(p.shape) == tuple(a.shape)
        p.set_data(NDArray(a))
    return net


def _step_matches_gradients(reference, config, model_config, params0, ids,
                            labels, want_loss, want):
    """One step of plain SGD at rate 1 without decay, so that the
    step's change IS the gradient, through the entry point the
    benchmark drives (`remat=True`): the loss to 1e-5 and every leaf's
    gradient to 2e-4 of its largest element (or of the median leaf's)
    against the reference's.  Returns (net, trainer)."""
    from mxnet_tpu.parallel import data_parallel

    net = _filled(model_config, params0)
    trainer = data_parallel.DataParallelTrainer(
        net, lambda out, _: out, "sgd", {"learning_rate": 1.0, "wd": 0.0},
        mesh=_one_device_mesh(), remat=True)
    loss = float(trainer.step((ids, labels),
                              np.zeros((len(ids),), np.float32)).asnumpy())
    assert abs(loss - float(want_loss)) < 1e-5 * abs(float(want_loss))
    names = [name for name, _ in net._ordered_params()]
    assert len(names) == len(want) == len(reference.leaf_parts(config))
    scale = np.median([float(np.abs(np.asarray(g)).max()) for g in want[1:]])
    for name, p0, p1, g in zip(names[1:], params0[1:], trainer._params[1:],
                               want[1:]):
        got = np.asarray(p0) - np.asarray(p1)
        err = np.abs(got - np.asarray(g)).max()
        assert err < 2e-4 * max(np.abs(np.asarray(g)).max(), scale), name
    return net, trainer


@pytest.mark.parametrize("router_width,first", [(16, 0), (64, 16)],
                         ids=["whole", "share"])
def test_model_matches_reference_loss_and_every_gradient(
        reference, router_width, first):
    import jax

    config = copy.deepcopy(SMALL)
    config.update(router_width=router_width, first_expert=first)
    params0 = _weights(reference, config)
    ids, labels = _batch(config)
    (want_loss, want_rows), want = jax.value_and_grad(
        reference.loss_and_routing, has_aux=True)(
            list(params0), ids, labels, config=config, precision="float32")

    net, trainer = _step_matches_gradients(
        reference, config, config, params0, ids, labels, want_loss, want)
    # the routing log: the rows each held expert got, layer by layer
    np.testing.assert_array_equal(np.asarray(trainer._params[0]),
                                  np.asarray(want_rows))
    # the block's own log is stale under a trainer: the live values
    assert net.routing_rows()[2].sum() == 0
    rows = net.routing_rows(trainer.aux_params()[net.routing_log.name])
    assert sorted(rows) == [1, 2]
    np.testing.assert_array_equal(rows[2], np.asarray(want_rows)[1])
    assert float(np.asarray(want_rows)[0].sum()) == ids.size * 2


@pytest.mark.parametrize("router_width,first,seq", [
    (16, 0, 64), (64, 16, 96)], ids=["whole", "share_padded_chunk"])
def test_hybrid_model_matches_reference_loss_and_every_gradient(
        hybrid, router_width, first, seq):
    """The chunked rule inside the model against the reference's token
    scan, with the convolution, the l2 norms, the gated norm, the
    zero-centred norms, q/k norms, the element-wise output gate and the
    gated shared expert around it; 96 tokens are a chunk and a half.
    float32: 2e-4 of a leaf's largest gradient (or of the median
    leaf's), as the laguna model; the state in bf16 reads 1e-2."""
    import jax

    reference, decoder_config = hybrid
    config = copy.deepcopy(SMALL_HYBRID)
    config.update(router_width=router_width, first_expert=first)
    params0 = _weights(reference, config)
    ids, labels = _batch(config, seq=seq)
    (want_loss, want_rows), want = jax.value_and_grad(
        reference.loss_and_routing, has_aux=True)(
            list(params0), ids, labels, config=config, precision="float32")

    net, trainer = _step_matches_gradients(
        reference, config, decoder_config(config), params0, ids, labels,
        want_loss, want)
    names = [name for name, _ in net._ordered_params()]
    assert sum("qkvz" in n for n in names) == 3 and sum(
        "q_norm" in n for n in names) == 1
    assert all(np.abs(np.asarray(g)).max() > 0 for g in want[1:])
    np.testing.assert_array_equal(np.asarray(trainer._params[0]),
                                  np.asarray(want_rows))
    # every one of the four layers has an expert layer and logs it
    rows = net.routing_rows(trainer.aux_params()[net.routing_log.name])
    assert sorted(rows) == [0, 1, 2, 3]


# Nemotron-H at a small size, in the PUBLISHED key names of
# NVIDIA-Nemotron-3-Nano-30B-A3B's config.json: layers of one part
# (Mamba-2, expert layer, NoPE attention) by `hybrid_override_pattern`
SMALL_NEMOTRON = dict(
    vocab_size=128, hidden_size=64, head_dim=16, num_attention_heads=4,
    num_key_value_heads=2, mamba_num_heads=4, mamba_head_dim=16,
    n_groups=2, ssm_state_size=16, chunk_size=32, conv_kernel=4,
    use_conv_bias=True, num_hidden_layers=5, hybrid_override_pattern="MEM*E",
    moe_intermediate_size=32, moe_shared_expert_intermediate_size=48,
    n_routed_experts=4, router_width=16, first_expert=4,
    num_experts_per_tok=3, routed_scaling_factor=2.5,
    layer_norm_epsilon=1e-5,
    assumed={"init_stdev": 0.05, "conv_init_stdev": 0.25,
             "a_log_stdev": 3.0,
             "optimizer": {"name": "adamw", "learning_rate": 1e-3,
                           "wd": 0.01, "beta1": 0.9, "beta2": 0.999,
                           "epsilon": 1e-8}})


@pytest.fixture(scope="module")
def nemotron():
    return (_bench_module("reference", "nemotron3_nano_30b"),
            _bench_module("configs", "nemotron3_nano_30b").decoder_config)


def test_one_part_layers_match_reference_loss_and_every_gradient(nemotron):
    """Layers of one part each: the chunked SSD inside the Mamba-2 mixer
    (biased convolution, gated group norm) against the reference's
    token scan, NoPE attention with no gate, the sigmoid-routed relu^2
    expert layer with its shared expert; 80 tokens are two chunks and a
    half.  float32, 2e-4 as the other models."""
    import jax

    reference, decoder_config = nemotron
    config = copy.deepcopy(SMALL_NEMOTRON)
    params0 = _weights(reference, config)
    ids, labels = _batch(config, rows=2, seq=80)
    (want_loss, want_rows), want = jax.jit(jax.value_and_grad(
        lambda *a: reference.loss_and_routing(
            *a, config=config, precision="float32"), has_aux=True))(
                list(params0), ids, labels)

    net, trainer = _step_matches_gradients(
        reference, config, decoder_config(config), params0, ids, labels,
        want_loss, want)
    names = [name for name, _ in net._ordered_params()]
    assert sum("in_proj" in n for n in names) == 2
    assert sum("ffn_norm" in n for n in names) == 2
    assert not any("gate_weight" in n or "q_norm" in n for n in names)
    assert all(np.abs(np.asarray(g)).max() > 0 for g in want[3:])
    np.testing.assert_array_equal(np.asarray(trainer._params[0]),
                                  np.asarray(want_rows))
    # the correction bias is held, not trained, and every layer is
    # recomputed (the trainer's `remat` skips a child with a frozen leaf)
    assert not trainer._trainable[1] and np.asarray(
        trainer._params[1]).max() == 0
    assert trainer._remat_count == 6
    rows = net.routing_rows(trainer.aux_params()[net.routing_log.name])
    assert sorted(rows) == [1, 4]


def test_hybrid_config_keys_default_to_the_plain_decoder():
    """Every key the hybrid model added defaults to the older
    behaviour: per-head gate, plain gains from 1, no q/k norm, no gate
    on the shared expert; an unknown gate is refused."""
    from mxnet_tpu.models import decoder_lm

    layer = decoder_lm.DecoderLayer(SMALL, 1)
    assert layer._gate == "per_head" and not layer._qk_norm
    assert not layer._zero_centered and not layer._shared_gate
    assert layer.gate_weight.shape == (16, 64)
    assert not hasattr(layer, "q_norm")
    with pytest.raises(ValueError, match="per_head or elementwise"):
        decoder_lm.DecoderLayer(dict(SMALL, attention_output_gate="x"), 0)


@pytest.mark.parametrize("model", ["mixed_attention", "hybrid"])
def test_trainer_in_bfloat16_with_remat_learns(reference, hybrid, model):
    from mxnet_tpu.parallel import data_parallel

    if model == "hybrid":
        config = copy.deepcopy(SMALL_HYBRID)
        net = _filled(hybrid[1](config), _weights(hybrid[0], config))
    else:
        config = copy.deepcopy(SMALL)
        net = _filled(config, _weights(reference, config))
    trainer = data_parallel.DataParallelTrainer(
        net, lambda out, _: out, "adamw",
        {"learning_rate": 1e-2, "wd": 0.01}, mesh=_one_device_mesh(),
        compute_dtype="bfloat16", remat=True)
    x, y = _batch(config), np.zeros((4,), np.float32)
    losses = [float(trainer.step(x, y).asnumpy()) for _ in range(6)]
    assert np.isfinite(losses).all() and losses[-1] < losses[0] - 0.05


def _sub_jaxprs(eqn):
    for value in eqn.params.values():
        for v in value if isinstance(value, (tuple, list)) else (value,):
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                yield inner


def _kernels(jaxpr, inside_remat=False):
    """(name of the kernel's function, whether a `remat2` body holds
    it) of every `pallas_call` of a jaxpr, at any depth."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append((eqn.params["jaxpr"].debug_info.func_name,
                        inside_remat))
        for inner in _sub_jaxprs(eqn):
            out += _kernels(inner,
                            inside_remat or eqn.primitive.name == "remat2")
    return out


@pytest.mark.parametrize("policy", ["kept", "bare"])
def test_remat_recomputes_a_layer_without_its_forward_kernel(
        reference, policy, monkeypatch):
    """The step's jaxpr (the dispatch traces the TPU branch whatever
    the platform): the forward attention kernel once a layer, in the
    forward pass, and none inside a `remat2` body, which holds the two
    backward kernels and takes the named output and row statistic as
    inputs; under a bare `jax.checkpoint` every body runs it again."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.parallel import data_parallel

    if policy == "bare":
        monkeypatch.setattr(data_parallel, "_remat_policy", lambda: None)
    config = copy.deepcopy(SMALL)
    config.update(head_dim=64, sliding_window=32)
    net = _filled(config, _weights(reference, config))
    trainer = data_parallel.DataParallelTrainer(
        net, lambda out, _: out, "adamw", {"learning_rate": 1e-3},
        mesh=_one_device_mesh(), compute_dtype="bfloat16", remat=True)
    x, y = _batch(config, rows=2, seq=128), np.zeros((2,), np.float32)
    trainer.build(x)
    scalar = jnp.zeros((), jnp.float32)
    jaxpr = jax.make_jaxpr(trainer._step_core)(
        trainer._params, trainer._states, x, y, jax.random.key_data(
            jax.random.key(0)), scalar, scalar).jaxpr
    kernels = _kernels(jaxpr)
    layers = config["num_hidden_layers"]
    again = layers if policy == "bare" else 0
    assert sorted(kernels) == sorted(
        [("_grouped_fwd_kernel", False)] * layers
        + [("_grouped_fwd_kernel", True)] * again
        + [("_grouped_dq_kernel", True), ("_grouped_dkv_kernel", True)]
        * layers)


def _moe_inputs(tokens=48, h=64, width=32, router_width=16, seed=0):
    rng = np.random.RandomState(seed)
    import jax.numpy as jnp

    return (jnp.asarray(rng.randn(tokens, h), jnp.float32),
            jnp.asarray(rng.randn(h, router_width) * 0.3, jnp.float32),
            jnp.asarray(rng.randn(router_width, h, 2 * width) * 0.1,
                        jnp.float32),
            jnp.asarray(rng.randn(router_width, width, h) * 0.1,
                        jnp.float32))


def _dense_layer(x, router, w_in, w_out, top_k, scale, held=None):
    """The uncut layer (or the part of it that the experts `held`
    give), expert by expert with masks."""
    import jax
    import jax.numpy as jnp

    probs = jax.nn.softmax(jnp.matmul(x, router, precision="highest"), -1)
    top, experts = jax.lax.top_k(probs, top_k)
    weights = top / top.sum(-1, keepdims=True) * scale
    out = jnp.zeros_like(x)
    for e in range(router.shape[1]) if held is None else held:
        gate, up = jnp.split(
            jnp.matmul(x, w_in[e], precision="highest"), 2, axis=-1)
        y = jnp.matmul(jax.nn.silu(gate) * up, w_out[e], precision="highest")
        out += jnp.where(experts == e, weights, 0).sum(-1, keepdims=True) * y
    return out


@pytest.mark.parametrize("shares,width,top_k,scale,shared", [
    (4, 16, 2, 2.5, False), (32, 64, 10, 1.0, True)],
    ids=["4_shares_top_2", "32_shares_top_10_gated_shared_expert"])
def test_shares_of_the_expert_layer_add_up_to_the_uncut_layer(
        shares, width, top_k, scale, shared):
    """Each share routes over the whole router and computes its own
    experts' part; the parts add up to the whole layer.  With a gated
    shared expert, which every chip computes alike, that part is
    counted ONCE beside the 32 routed parts."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.moe import _k_moe_ffn

    x, router, w_in, w_out = _moe_inputs(router_width=width)
    held = width // shares
    parts, rows = [], []
    for first in range(0, width, held):
        y, log = _k_moe_ffn(x, router, w_in[first:first + held],
                            w_out[first:first + held], first_expert=first,
                            top_k=top_k, scale=scale)
        parts.append(np.asarray(y))
        rows.append(np.asarray(log))
        assert log[:held].sum() + log[held] == x.shape[0] * top_k
    want = np.asarray(_dense_layer(x, router, w_in, w_out, top_k, scale))
    got = sum(parts)
    if shared:
        import mxnet_tpu as mx

        rng = np.random.RandomState(9)
        s_in, s_out, s_gate = (rng.randn(*shape).astype(np.float32) * 0.1
                               for shape in ((64, 64), (64, 32), (1, 64)))
        nd = mx.nd.array
        fc = lambda a, w: mx.nd.FullyConnected(  # noqa: E731
            a, nd(w), no_bias=True, flatten=False, num_hidden=w.shape[0])
        # the program's ops, once
        once = (fc(mx.nd.swiglu(fc(nd(np.asarray(x)), s_in)), s_out)
                * mx.nd.sigmoid(fc(nd(np.asarray(x)), s_gate))).asnumpy()
        got = got + once
        gate, up = jnp.split(jnp.matmul(x, s_in.T, precision="highest"), 2,
                             axis=-1)
        want = want + np.asarray(
            jax.nn.sigmoid(jnp.matmul(x, s_gate.T, precision="highest"))
            * jnp.matmul(jax.nn.silu(gate) * up, s_out.T,
                         precision="highest"))
        # counted in every share it would be 32 times too much
        assert np.abs(got + (shares - 1) * once - want).max() > 1e-2
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    assert sum(r[:held].sum() for r in rows) == x.shape[0] * top_k
    # no share is the whole: each leaves the others' part out
    assert all(np.abs(p - sum(parts)).max() > 1e-3 for p in parts)


def _dense_sigmoid_layer(x, router, w_in, w_out, top_k, scale, bias,
                         held=None):
    """The uncut sigmoid-routed relu^2 layer (or the part of it that the
    experts `held` give), expert by expert with masks."""
    import jax
    import jax.numpy as jnp

    scores = jax.nn.sigmoid(jnp.matmul(x, router, precision="highest"))
    experts = jax.lax.top_k(scores + bias, top_k)[1]
    weights = jnp.take_along_axis(scores, experts, -1)
    weights = weights / weights.sum(-1, keepdims=True) * scale
    out = jnp.zeros_like(x)
    for e in range(router.shape[1]) if held is None else held:
        hidden = jnp.maximum(jnp.matmul(x, w_in[e], precision="highest"), 0)
        y = jnp.matmul(hidden * hidden, w_out[e], precision="highest")
        out += jnp.where(experts == e, weights, 0).sum(-1, keepdims=True) * y
    return out


def _sigmoid_inputs(router_width=16, seed=0):
    import jax.numpy as jnp

    x, router, w_in, w_out = _moe_inputs(router_width=router_width,
                                         seed=seed)
    # relu^2 experts: no [gate | up] packing
    return x, router, w_in[:, :, :32], w_out, jnp.asarray(
        np.random.RandomState(seed + 1).rand(router_width) * 0.4,
        jnp.float32)


def test_sigmoid_relu2_moe_matches_the_dense_form_values_and_gradients():
    """Sigmoid scoring, a correction bias that CHANGES the choice (and
    takes no gradient), relu^2 experts with no gate, over held experts
    4-7 of a 16-wide router: the op's part and its gradients against
    the dense loop's."""
    import jax

    from mxnet_tpu.ops.moe import _k_moe_ffn

    x, router, w_in, w_out, bias = _sigmoid_inputs()

    def op(x, router, w_in, w_out, bias):
        return _k_moe_ffn(x, router, w_in[4:8], w_out[4:8], bias,
                          first_expert=4, top_k=3, scale=2.5,
                          scoring="sigmoid", activation="relu2")

    def dense(x, router, w_in, w_out, bias):
        return _dense_sigmoid_layer(x, router, w_in, w_out, 3, 2.5, bias,
                                    held=range(4, 8))

    op, dense = jax.jit(op), jax.jit(dense)
    y, log = op(x, router, w_in, w_out, bias)
    np.testing.assert_allclose(np.asarray(y), np.asarray(
        dense(x, router, w_in, w_out, bias)), rtol=2e-5, atol=2e-6)
    unbiased = op(x, router, w_in, w_out, 0 * bias)[1]
    assert float(np.abs(np.asarray(log) - np.asarray(unbiased)).sum()) > 0
    assert float(log.sum()) == x.shape[0] * 3

    def grads(fn):
        return jax.jit(jax.grad(lambda *a: (fn(*a) ** 2).sum(),
                                argnums=(0, 1, 2, 3, 4)))(
            x, router, w_in, w_out, bias)

    got = grads(lambda *a: op(*a)[0])
    want = grads(dense)
    for g, w in zip(got[:4], want[:4]):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-4,
                                   atol=2e-5)
    assert not np.asarray(got[4]).any()
    with pytest.raises(ValueError, match="scoring"):
        _k_moe_ffn(x, router, w_in, w_out, scoring="tanh")


def test_sixteen_shares_of_eight_experts_add_up_to_the_uncut_layer():
    """The deployment of the `nemotron3_nano_30b` cell at a small size:
    a 128-wide sigmoid router, 16 shares of 8 relu^2 experts, each
    routing over the whole router and computing its own experts' part;
    with the shared expert, which every chip computes alike, counted
    ONCE, the parts add up to the uncut layer."""
    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu.ops.moe import _k_moe_ffn

    x, router, w_in, w_out, bias = _sigmoid_inputs(router_width=128)
    firsts = range(0, 128, 8)
    shares = jax.jit(lambda x, router, w_in, w_out, bias: [_k_moe_ffn(
        x, router, w_in[f:f + 8], w_out[f:f + 8], bias, first_expert=f,
        top_k=6, scale=2.5, scoring="sigmoid", activation="relu2")
        for f in firsts])
    parts = []
    for y, log in shares(x, router, w_in, w_out, bias):
        parts.append(np.asarray(y))
        assert log[:8].sum() + log[8] == x.shape[0] * 6
    rng = np.random.RandomState(9)
    s_in, s_out = (rng.randn(*shape).astype(np.float32) * 0.1
                   for shape in ((48, 64), (64, 48)))
    nd = mx.nd.array
    fc = lambda a, w: mx.nd.FullyConnected(  # noqa: E731
        a, nd(w), no_bias=True, flatten=False, num_hidden=w.shape[0])
    once = fc(mx.nd.squared_relu(fc(nd(np.asarray(x)), s_in)),
              s_out).asnumpy()
    hidden = jnp.maximum(jnp.matmul(x, s_in.T, precision="highest"), 0)
    want = np.asarray(_dense_sigmoid_layer(x, router, w_in, w_out, 6, 2.5,
                                           bias)
                      + jnp.matmul(hidden * hidden, s_out.T,
                                   precision="highest"))
    np.testing.assert_allclose(sum(parts) + once, want, rtol=2e-5,
                               atol=2e-6)
    # counted in every share it would be 16 times too much
    assert np.abs(sum(parts) + 16 * once - want).max() > 1e-2
    assert all(np.abs(p - sum(parts)).max() > 1e-3 for p in parts)


def test_every_assignment_is_computed_when_all_tokens_pick_one_expert():
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.moe import _k_moe_ffn

    x, router, w_in, w_out = _moe_inputs()
    x = jnp.abs(x)
    # expert 5 wins every token by a wide margin, expert 6 comes second
    router = jnp.zeros_like(router).at[:, 5].set(1.0).at[:, 6].set(0.5)

    def loss(x, w_in, w_out, fn):
        return (fn(x, w_in, w_out) ** 2).sum()

    held = _experts_4_to_7(_k_moe_ffn, router)
    whole = lambda x, w_in, w_out: _dense_layer(  # noqa: E731
        x, router, w_in, w_out, 2, 2.5)
    y, log = _k_moe_ffn(x, router, w_in[4:8], w_out[4:8], first_expert=4,
                        top_k=2, scale=2.5)
    np.testing.assert_array_equal(np.asarray(log),
                                  [0, x.shape[0], x.shape[0], 0, 0])
    np.testing.assert_allclose(np.asarray(y), np.asarray(whole(x, w_in,
                                                               w_out)),
                               rtol=2e-5, atol=2e-6)
    got = jax.grad(loss, argnums=(0, 1, 2))(x, w_in, w_out, held)
    want = jax.grad(loss, argnums=(0, 1, 2))(x, w_in, w_out, whole)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4,
                                   atol=1e-5)


def _experts_4_to_7(op, router):
    """The op over experts 4-7 with full-size expert operands (so its
    gradients have the uncut layer's shapes)."""
    def fn(x, w_in, w_out):
        return op(x, router, w_in[4:8], w_out[4:8], first_expert=4,
                  top_k=2, scale=2.5)[0]
    return fn


def test_moe_gradients_match_the_dense_form_on_an_uneven_routing():
    import jax

    from mxnet_tpu.ops.moe import _k_moe_ffn

    x, router, w_in, w_out = _moe_inputs(seed=4)

    def op_loss(x, router, w_in, w_out):
        return (_k_moe_ffn(x, router, w_in, w_out, first_expert=0, top_k=2,
                           scale=2.5)[0] ** 2).sum()

    def dense_loss(x, router, w_in, w_out):
        return (_dense_layer(x, router, w_in, w_out, 2, 2.5) ** 2).sum()

    got = jax.grad(op_loss, argnums=(0, 1, 2, 3))(x, router, w_in, w_out)
    want = jax.grad(dense_loss, argnums=(0, 1, 2, 3))(x, router, w_in, w_out)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-4,
                                   atol=2e-5)


def _routed(rows_here):
    """Inputs that send `rows_here` assignments to the held experts
    4-7: the first third of them from tokens that send BOTH of theirs
    (to 4, 5 or 6 in turn, and to 7), the rest from tokens that send
    one (to 4, 5 or 6); every other assignment goes to the absent
    experts 12 and 13.  The first five features of x say which, the
    rest is noise the router hardly reads."""
    import jax.numpy as jnp

    x, router, w_in, w_out = map(np.array, _moe_inputs(seed=5))
    both = rows_here // 3
    senders = rows_here - both
    assert senders <= x.shape[0]
    x[:, :6] = 0.0
    x[np.arange(senders), np.arange(senders) % 3] = 1.0
    x[:both, 3] = 1.0
    x[:, 4] = 1.0
    router *= 0.05
    router[:6] = 0.0
    router[[0, 1, 2], [4, 5, 6]] = 3.0
    router[3, 7] = 2.5
    router[4, 12], router[4, 13] = 2.0, 1.5
    return tuple(jnp.asarray(a) for a in (x, router, w_in, w_out))


@pytest.mark.parametrize("rows_here,capacity", [
    (0, 12), (7, 12), (12, 12), (13, 24), (24, 24), (25, 96), (None, 96)],
    ids=["no_row", "in_the_smallest", "fills_the_smallest",
         "just_over_the_smallest", "fills_the_second",
         "just_over_the_second", "all_on_one_expert"])
def test_moe_at_every_capacity_matches_the_dense_form_and_the_top_branch(
        monkeypatch, rows_here, capacity):
    """48 tokens x top-2 compile the expert path at 12, 24 and 96 rows;
    the routing decides on the device which of them runs.  Whichever
    does, the output and all four gradients are the dense form's and
    the top capacity's (today's path: every assignment's row)."""
    import jax

    from mxnet_tpu.ops import moe

    if rows_here is None:       # every assignment on held experts 5 and 6
        x, router, w_in, w_out = _moe_inputs()
        x = abs(x)
        router = router.at[:].set(0.0).at[:, 5].set(1.0).at[:, 6].set(0.5)
        rows_here = 2 * x.shape[0]
    else:
        x, router, w_in, w_out = _routed(rows_here)
    assert moe.capacities(2 * x.shape[0]) == (12, 24, 96)
    assert moe.capacity(rows_here, 2 * x.shape[0]) == capacity

    def op(x, router, w_in, w_out):
        return moe._k_moe_ffn(x, router, w_in[4:8], w_out[4:8],
                              first_expert=4, top_k=2, scale=2.5)

    def dense(x, router, w_in, w_out):
        return _dense_layer(x, router, w_in, w_out, 2, 2.5, held=range(4, 8))

    def value_and_grads(fn):
        return jax.value_and_grad(
            lambda *args: (fn(*args) ** 2).sum(), argnums=(0, 1, 2, 3))(
                x, router, w_in, w_out)

    y, log = op(x, router, w_in, w_out)
    assert log[:4].sum() == rows_here and log.sum() == 2 * x.shape[0]
    # every capacity is compiled; which of them runs is `capacity`'s
    # choice, by the one rule the op's own switch follows
    text = str(jax.make_jaxpr(op)(x, router, w_in, w_out))
    assert all(f"[{c},64]" in text for c in (12, 24, 96))
    got = value_and_grads(lambda *args: op(*args)[0])
    want = value_and_grads(dense)
    monkeypatch.setattr(moe, "_CAPACITY_SHARES", (1,))
    assert moe.capacities(2 * x.shape[0]) == (96,)
    top = value_and_grads(lambda *args: op(*args)[0])
    np.testing.assert_allclose(np.asarray(y), np.asarray(
        dense(x, router, w_in, w_out)), rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(y), np.asarray(
        op(x, router, w_in, w_out)[0]), rtol=2e-5, atol=2e-6)
    for g, w, t in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                       jax.tree.leaves(top)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-4,
                                   atol=2e-5)
        np.testing.assert_allclose(np.asarray(g), np.asarray(t), rtol=2e-5,
                                   atol=2e-6)


@pytest.mark.parametrize("capacity", [96, 192, 768])
def test_dispatch_and_combine_are_transposes_at_a_capacity(capacity):
    """<combine(rows), g> == <rows, dispatch(g)> for any rows and g, at
    the two token-ordered capacities (the second longer than one block
    of the run sums) and at the top one, and each is the other's
    registered vjp."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import moe

    rng = np.random.RandomState(capacity)
    tokens, top_k, held, rows_here = 96, 8, 4, min(capacity, 150)
    assert moe.capacities(tokens * top_k) == (96, 192, 768)
    # the live assignments crowd on the first 24 tokens (runs of up to
    # 8 rows a token); the rest go to absent experts
    group = np.full(tokens * top_k, held)
    group[rng.permutation(24 * top_k)[:rows_here]] = rng.randint(
        0, held, rows_here)
    order = jnp.argsort(jnp.asarray(group), stable=True)
    inverse = jnp.argsort(order)
    here = jnp.asarray(group < held).reshape(tokens, top_k)
    plan = moe._plan(capacity, order, inverse, here, rows_here, top_k)
    rows = jnp.asarray(rng.randn(capacity, 8), jnp.float32)
    g = jnp.asarray(rng.randn(tokens, 8), jnp.float32)
    back = moe._combine(rows, plan, top_k)
    out = moe._dispatch(g, plan, top_k)
    assert back.shape == g.shape and out.shape == rows.shape
    np.testing.assert_allclose(float((back * g).sum()),
                               float((rows * out).sum()), rtol=1e-5)
    # rows past the live ones are neither read nor written
    assert not np.asarray(out[rows_here:]).any()
    np.testing.assert_array_equal(np.asarray(back), np.asarray(moe._combine(
        rows.at[rows_here:].set(np.nan), plan, top_k)))
    np.testing.assert_allclose(
        np.asarray(jax.vjp(lambda r: moe._combine(r, plan, top_k), rows)[1](
            g)[0]), np.asarray(out), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(jax.vjp(lambda v: moe._dispatch(v, plan, top_k), g)[1](
            rows)[0]), np.asarray(back), rtol=1e-6)


@pytest.mark.parametrize("kind", ["full_attention", "sliding_attention"])
def test_rotary_embedding_matches_reference_tables(reference, kind):
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu.ops.nn import rotary_frequencies

    config = SMALL
    rope = dict(config["rope_parameters"][kind])
    r = int(config["head_dim"] * rope.pop("partial_rotary_factor"))
    inv_freq, factor = rotary_frequencies(r, **rope)
    x = np.random.RandomState(1).randn(2, 3, 32, 16).astype(np.float32)
    got = mx.nd.rotary_embedding(mx.nd.array(x), inv_freq=inv_freq,
                                 attention_factor=factor).asnumpy()
    cos, sin, r_ref = reference.rotary_tables(config, kind, 32)
    assert r_ref == r == (8 if kind == "full_attention" else 16)
    want = np.asarray(reference._rotate(jnp.asarray(x), cos, sin, r))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(got[..., r:], x[..., r:])
    if kind == "full_attention":
        # YaRN: every frequency between the interpolated and the plain
        plain = np.asarray(rotary_frequencies(r, rope_theta=500000)[0])
        assert factor == pytest.approx(1.4158883083359672)
        assert (np.asarray(inv_freq) <= plain + 1e-12).all()
        assert (np.asarray(inv_freq) >= plain / 64 - 1e-12).all()


def test_rms_norm_and_swiglu_ops():
    import jax

    import mxnet_tpu as mx

    rng = np.random.RandomState(2)
    x = rng.randn(3, 5, 64).astype(np.float32)
    g = rng.rand(64).astype(np.float32) + 0.5
    got = mx.nd.rms_norm(mx.nd.array(x), mx.nd.array(g), eps=1e-6).asnumpy()
    want = x / np.sqrt((x ** 2).mean(-1, keepdims=True) + 1e-6) * g
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    half = x[..., :32]
    want = np.asarray(jax.nn.silu(half)) * x[..., 32:]
    np.testing.assert_allclose(mx.nd.swiglu(mx.nd.array(x)).asnumpy(), want,
                               rtol=1e-5, atol=1e-6)
    # differentiable through autograd
    a = mx.nd.array(x)
    a.attach_grad()
    with mx.autograd.record():
        y = mx.nd.rms_norm(a, mx.nd.array(g)).sum()
    y.backward()
    assert np.isfinite(a.grad.asnumpy()).all()


def test_moe_routing_section_is_on_metrics(reference):
    from mxnet_tpu import profiler
    from mxnet_tpu.models import decoder_lm
    from mxnet_tpu.parallel import data_parallel
    from mxnet_tpu.telemetry import metrics

    config = copy.deepcopy(SMALL)
    net = _filled(config, _weights(reference, config))
    trainer = data_parallel.DataParallelTrainer(
        net, lambda out, _: out, "sgd", {"learning_rate": 0.1, "wd": 0.0},
        mesh=_one_device_mesh())
    assert trainer not in data_parallel.live_trainers()   # not built yet
    ids, labels = _batch(config)
    trainer.step((ids, labels), np.zeros((len(ids),), np.float32))
    assert data_parallel.live_trainers()[-1] is trainer
    assert "moeRouting" in profiler.section_names()
    stats = decoder_lm.moe_routing_stats()
    mine = [f"trainer{trainer._serial}.layer{l}" for l in (1, 2)]
    assert set(mine) <= set(stats["rows_here"]) and stats["layers"] >= 2
    # SMALL holds all its experts: every assignment lands here
    assert stats["rows_here"][mine[0]] == ids.size * 2
    assert stats["share_here"][mine[0]] == 1.0
    assert stats["max_over_mean"][mine[0]] >= 1.0
    # and the expert path ran at its top capacity, full
    assert stats["capacity"][mine[0]] == ids.size * 2
    assert stats["capacity_share"][mine[0]] == 1.0
    assert decoder_lm.routing_stats([100, 28, 896])["capacity"] == 128
    assert decoder_lm.routing_stats([100, 29, 895])["capacity"] == 256
    assert decoder_lm.routing_stats([0, 0, 0])["capacity_share"] == 0.0
    assert sum(stats["rows_per_expert"][f"{mine[0]}.expert{e}"]
               for e in range(16)) == ids.size * 2
    assert list(decoder_lm.moe_routing_stats(newest=True)["rows_here"]) \
        == mine
    assert profiler.sections()["moeRouting"] == stats
    text = metrics.default_registry().render()
    assert "mxtpu_moe_routing_rows_here{key=" in text
    assert "mxtpu_moe_routing_capacity_share{key=" in text
    table = "\n".join(profiler._section_tables())
    assert "MoE Routing" in table and "filled 1.000" in table
    # window-scoped like every section: after a reset dump the trainer
    # is back with its next step; the accessor itself takes no window
    assert profiler.sections(reset=True)["moeRouting"] == stats
    assert profiler.sections()["moeRouting"]["layers"] == 0
    assert decoder_lm.moe_routing_stats() == stats
    trainer.step((ids, labels), np.zeros((len(ids),), np.float32))
    assert set(mine) <= set(profiler.sections()["moeRouting"]["rows_here"])
    # and the section loses the trainer with the trainer's life
    del trainer
    import gc

    gc.collect()
    assert not set(mine) & set(decoder_lm.moe_routing_stats()["rows_here"])
