"""Offline Mosaic validation: AOT-compile every Pallas kernel family
for a DESCRIBED TPU topology — no chip required.

jax.experimental.topologies hands out v5e device descriptions whose
jit/lower/compile path runs the real Mosaic + XLA:TPU compilers
locally (libtpu is in the image).  That converts "will Mosaic reject
this kernel?" from an on-chip question (chip_smoke.py's `kernels`
phase) into a CPU-box regression gate that runs in every suite — and it
is why kernel dispatch carries no compile probe: a shape the static
gates admit has been compiled for the chip here.

The topology is described inside a module-scoped FIXTURE, never at
import, in a skipif or in parametrize: only one process may load
libtpu, every xdist worker imports this file, and only the worker that
RUNS it may take the library.  Keep every such test in this one file.

Besides the toy-shape family sweep, the main path's kernels are
compiled at real widths: BERT-base attention (64, 12, 128, 64) with its
(b, 1, 1, sk) key-padding mask, alone, through the platform dispatch of
ops/attention, under automatic partitioning on a 4-device mesh (where
the compiler refuses a bare Mosaic call and parallel.mesh.
per_batch_shard wraps it), and inside the multi-replica whole step's
shard_map.
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — environment-dependent
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return NamedSharding(Mesh(np.array(topo.devices[:1]), ("d",)),
                         PartitionSpec())


@pytest.fixture(scope="module")
def four_chips(topo):
    """A dp=4 mesh over the described host, and its batch sharding."""
    mesh = Mesh(np.array(topo.devices), ("dp",))
    return mesh, NamedSharding(mesh, PartitionSpec("dp"))


def _aot_grad_compile(sharding, loss_fn, *specs):
    """value-and-grad of loss_fn AOT-compiled for the v5e target;
    returns the compiled program's text.  The value is kept: where a
    family's backward is plain XLA, the forward kernel is dead code in
    the gradient alone.  A Mosaic kernel must be in the program — a
    gate that routed to the XLA form would otherwise pass here by
    compiling something else."""
    jitted = jax.jit(jax.value_and_grad(loss_fn),
                     in_shardings=(sharding,) * len(specs),
                     out_shardings=sharding)
    text = jitted.lower(*specs).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("d,causal,masked", [
    (128, False, False), (128, True, False), (128, False, True),
    (64, False, False), (64, True, True),
])
def test_flash_attention_aot(one_chip, dt, d, causal, masked):
    from mxnet_tpu.ops.pallas.flash_attention import _flash_sdpa

    q = jax.ShapeDtypeStruct((1, 2, 256, d), dt)

    if masked:
        km = jnp.zeros((1, 256), jnp.float32)

        def loss(a):
            return _flash_sdpa(a, a, a, km, causal, 0.125) \
                .astype(jnp.float32).sum()
    else:
        def loss(a):
            return _flash_sdpa(a, a, a, None, causal, 0.125) \
                .astype(jnp.float32).sum()
    _aot_grad_compile(one_chip, loss, q)


@pytest.mark.parametrize("dt,causal", [
    (jnp.bfloat16, False), (jnp.bfloat16, True), (jnp.float32, True)],
    ids=["bf16", "bf16-causal", "f32-causal"])
def test_flash_streamed_long_context_aot(one_chip, dt, causal):
    """The STREAMED kernels (K/V swept by a grid dim) Mosaic-compile at
    seq 16384 — past the resident path's VMEM bound; single-chip
    long-context attention with no ceiling."""
    from mxnet_tpu.ops.pallas.flash_attention import _flash_sdpa

    q = jax.ShapeDtypeStruct((1, 1, 16384, 128), dt)

    def loss(a):
        return _flash_sdpa(a, a, a, None, causal, 0.125) \
            .astype(jnp.float32).sum()
    _aot_grad_compile(one_chip, loss, q)


@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_conv_fused_aot(one_chip, monkeypatch, dt):
    from mxnet_tpu.ops.pallas import batch_norm as pbn
    from mxnet_tpu.ops.pallas import conv_fused as cf

    # the family's gate asks jax.default_backend(), which is the CPU
    # here: steer it, or this compiles the jnp reference forms
    monkeypatch.setattr(cf, "_use_pallas", lambda: True)
    x = jax.ShapeDtypeStruct((512, 256), dt)
    w = jnp.zeros((256, 256), dt)
    sc = jnp.zeros((1, 256), dt)
    sh = jnp.zeros((1, 256), dt)
    _aot_grad_compile(
        one_chip, lambda a: cf.matmul_bn_stats(a, w)[0]
        .astype(jnp.float32).sum(), x)
    _aot_grad_compile(
        one_chip, lambda a: cf.bn_act_matmul(a, sc, sh, w)
        .astype(jnp.float32).sum(), x)
    _aot_grad_compile(
        one_chip, lambda a: cf.bn_act_matmul_stats(a, sc, sh, w)[0]
        .astype(jnp.float32).sum(), x)
    _aot_grad_compile(
        one_chip, lambda a: pbn.bn_stats(a)[0]
        .astype(jnp.float32).sum(), x)


@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_pallas_lstm_aot(one_chip, dt):
    from mxnet_tpu.ops.pallas.rnn import lstm_layer

    T, N, H = 4, 16, 128
    xp = jax.ShapeDtypeStruct((T, N, 4 * H), dt)
    wh = jnp.zeros((4 * H, H), dt)
    h0 = jnp.zeros((N, H), dt)
    c0 = jnp.zeros((N, H), dt)
    _aot_grad_compile(
        one_chip, lambda a: lstm_layer(a, wh, h0, c0)[0]
        .astype(jnp.float32).sum(), xp)


@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_pallas_gru_aot(one_chip, dt):
    from mxnet_tpu.ops.pallas.rnn import gru_layer

    T, N, H = 4, 16, 128
    xp = jax.ShapeDtypeStruct((T, N, 3 * H), dt)
    wh = jnp.zeros((3 * H, H), dt)
    bh = jnp.zeros((3 * H,), dt)
    h0 = jnp.zeros((N, H), dt)
    _aot_grad_compile(
        one_chip, lambda a: gru_layer(a, wh, bh, h0)[0]
        .astype(jnp.float32).sum(), xp)


@pytest.mark.slow
@pytest.mark.parametrize("family", ["resnet50", "bert_block"])
def test_whole_graph_aot(one_chip, family):
    """The full flagship forward graphs also Mosaic/XLA-compile for the
    v5e target (catches non-pallas lowering issues — layout, dtype,
    dynamic shapes — before any chip time is spent): the hybridize-time
    pure graph fn (CachedOp._build_fn) is AOT-jitted for the described
    topology, exactly the computation the chip would run."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.gluon.block import CachedOp

    if family == "resnet50":
        from mxnet_tpu.gluon.model_zoo import vision

        net = vision.resnet50_v1(layout="NHWC")
        x = nd.ones((4, 64, 64, 3))
    else:
        from mxnet_tpu.models.bert import BERTEncoderLayer

        net = BERTEncoderLayer(units=256, hidden_size=1024, num_heads=4)
        x = nd.ones((4, 32, 256))
    mx.random.seed(0)
    net.initialize(mx.init.Xavier())
    net(x)  # eager shape-inference pass materializes deferred params

    op = CachedOp(net)
    fn = op._build_fn(False)
    raws = [p.data()._data for _, p in net._ordered_params()]
    key = jax.random.PRNGKey(0)

    jitted = jax.jit(functools.partial(fn, _n_params=len(raws)),
                     in_shardings=one_chip, out_shardings=one_chip)
    jitted.lower(key, *raws, x._data).compile()


# ---------------------------------------------------------------------------
# the main path's kernels at real widths
# ---------------------------------------------------------------------------

BERT_ATTN = (64, 12, 128, 64)   # chip_smoke's batch x BERT-base heads


@pytest.mark.parametrize("shape,dt", [
    (BERT_ATTN, jnp.bfloat16),          # DataParallelTrainer bf16 step
    (BERT_ATTN, jnp.float32),           # gluon.Trainer whole step, fp32
    ((8, 12, 128, 64), jnp.float32),    # chip_smoke's gluon phase
    ((1, 2, 256, 192), jnp.bfloat16),   # the d % 64 rule past 64 and 128
    ((128, 12, 128, 64), jnp.bfloat16),  # the benchmark's bert_base.seq128
    ((32, 12, 512, 64), jnp.bfloat16),  # bert_base.seq512, the cell to come
], ids=["bert-bf16", "bert-f32", "bert-b8-f32", "d192-bf16",
        "bert-seq128-cell", "bert-seq512-cell"])
def test_flash_real_width_aot(one_chip, shape, dt):
    """Flash fwd+bwd with the key-padding mask at the shapes the static
    gate admits on the main path — what the deleted dispatch-time
    compile probe used to ask the chip at a toy shape.  Three kernels
    an attention, and each one's FIRST operand is the (b*h, s, d)
    query in the operands' dtype: the benchmark's `flash_attn_roofline`
    finds the kernels' device events by that shape, so the heads a grid
    step works on are cut by the BlockSpec, never by a reshape of q in
    front of the call."""
    import re

    from mxnet_tpu.ops.pallas.flash_attention import _flash_sdpa, _tiles_ok

    q = jax.ShapeDtypeStruct(shape, dt)
    assert _tiles_ok(q, q)
    km = jnp.zeros((shape[0], shape[2]), jnp.float32)

    def loss(a):
        return _flash_sdpa(a, a, a, km, False, shape[3] ** -0.5) \
            .astype(jnp.float32).sum()

    text = _aot_grad_compile(one_chip, loss, q)
    assert text.count("tpu_custom_call") == 3   # fwd, dq, dk/dv
    b, h, s, d = shape
    hlo_type = {jnp.bfloat16: "bf16", jnp.float32: "f32"}[dt]
    want = f"{hlo_type}[{b * h},{s},{d}]"
    shapes = dict(re.findall(r"%([\w.\-]+) = (\w+\[[\d,]*\])", text))
    calls = [line for line in text.splitlines()
             if "custom-call(" in line and "tpu_custom_call" in line]
    for line in calls:
        first = re.search(r"custom-call\(%([\w.\-]+)", line).group(1)
        assert shapes[first] == want, (first, shapes[first], want)


@pytest.mark.parametrize("dt", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("heads,window", [(48, None), (64, 512)],
                         ids=["full-48", "window-64"])
def test_grouped_flash_at_the_laguna_cells_shapes_aot(one_chip, heads,
                                                      window, dt):
    """The grouped kernels (8 K/V heads under 48 or 64 query heads, a
    window of 512 beside full causal attention) at the shapes of the
    `laguna_xs2.seq8k` cell, forward and both backward kernels; in
    float32 too, which is what the trainer's eager probe of the model
    runs (the XLA form's scores would be 26 GB there)."""
    from mxnet_tpu.ops.pallas.flash_attention import flash_attention

    q = jax.ShapeDtypeStruct((2, heads, 8192, 128), dt)
    kv = jax.ShapeDtypeStruct((2, 8, 8192, 128), dt)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window) \
            .astype(jnp.float32).sum()

    jitted = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                     in_shardings=(one_chip,) * 3)
    text = jitted.lower(q, kv, kv).compile().as_text()
    assert text.count("tpu_custom_call") == 3


@pytest.mark.parametrize("dt", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_grouped_flash_at_head_size_256_aot(one_chip, dt):
    """The grouped kernels at the `qwen3_next_80b.seq8k` cell's shape:
    16 query heads over 2 K/V heads (a group of 8) of head size 256, 2
    x 8,192 tokens.  `_grouped_ok` admits it by its byte rule (a K/V
    head of 8,192 x 256 in float32 is 16.8 MB twice over, inside the 32
    MiB); a fall to the XLA form would materialise 16 x 8,192^2 float32
    scores a sequence, and passes here only by being three Mosaic
    calls: forward, dQ, dK/dV."""
    from mxnet_tpu.ops.pallas.flash_attention import flash_attention

    q = jax.ShapeDtypeStruct((2, 16, 8192, 256), dt)
    kv = jax.ShapeDtypeStruct((2, 2, 8192, 256), dt)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True) \
            .astype(jnp.float32).sum()

    jitted = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                     in_shardings=(one_chip,) * 3)
    text = jitted.lower(q, kv, kv).compile().as_text()
    assert text.count("tpu_custom_call") == 3


def test_delta_rule_at_the_qwen_cells_shape_holds_no_state_a_token_aot(
        one_chip):
    """`gated_delta_rule` at (2, 32, 8,192, 128) over 16 key heads in
    bf16, forward and backward: it compiles for v5e, its largest
    float32 array is the states at the chunks' starts of one head group
    (128 x 2 x 8 x 128 x 128), nothing the size of a state a token
    (8,192 x 64 KB a head), and its temporaries stay under 2 GB (1.25
    with the intra-chunk kernels; 2.37 while JAX differentiated through
    the inverse; 7.0 before the heads were worked on in groups).  The
    intra-chunk part is three Mosaic calls (the forward kernel in the
    pass and in a group's recomputation, the backward kernel), each
    under `/delta_rule/`, where `delta_rule_roofline` and
    `linear_attn_ms` look for the rule's events; no (2, 8, 128, 64, 64)
    float32 array of the inverse's products is left."""
    import re

    from mxnet_tpu.ops.linear_attention import (_k_gated_delta_rule,
                                                head_groups)

    qk = jax.ShapeDtypeStruct((2, 16, 8192, 128), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((2, 32, 8192, 128), jnp.bfloat16)
    gb = jax.ShapeDtypeStruct((2, 32, 8192), jnp.float32)
    assert head_groups(2, 16, 32) == 4

    def loss(q, k, v, g, beta):
        # inside another scope, as in the trainer's step: the outermost
        # scope of a differentiated function is named `jvp(<scope>)`
        with jax.named_scope("forward"):
            return _k_gated_delta_rule(q, k, v, g, beta) \
                .astype(jnp.float32).sum()

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)),
                       in_shardings=(one_chip,) * 5) \
        .lower(qk, qk, v, gb, gb).compile()
    text = compiled.as_text()
    sizes = {int(np.prod([int(n) for n in dims.split(",")]))
             * (4 if kind == "f32" else 2)
             for kind, dims in re.findall(r"\b(f32|bf16)\[([0-9,]+)\]", text)}
    states_kept = 128 * 2 * 8 * 128 * 128 * 4
    assert states_kept in sizes
    assert max(sizes) <= 2 * states_kept, max(sizes)
    assert compiled.memory_analysis().temp_size_in_bytes < 2e9
    assert "/delta_rule/" in text and "while" in text
    kernels = re.findall(
        r'custom_call_target="tpu_custom_call"[^\n]*op_name="([^"]*)"', text)
    assert len(kernels) == text.count("tpu_custom_call") == 3
    assert all("/delta_rule/" in name for name in kernels), kernels
    assert sum("transpose(" in name for name in kernels) == 2
    assert "f32[2,8,128,64,64]" not in text


@pytest.mark.parametrize("dt,dk,dv", [
    (jnp.float32, 128, 256), (jnp.bfloat16, 128, 256),
    (jnp.float32, 512, 512)], ids=["f32", "bf16", "f32-heads-of-512"])
def test_delta_rule_kernels_aot(one_chip, dt, dk, dv):
    """The rule's two kernels in both dtypes at a short sequence, key
    heads under value heads, one head group and 12 pairs of chunks (a
    block of 6 a grid step; of 3 at float32 heads of 512, whose blocks
    would not fit the kernels' VMEM at 6: the block is sized from the
    bytes); lowered for the CPU the same call holds no kernel."""
    from mxnet_tpu.ops.linear_attention import _k_gated_delta_rule

    qk = jax.ShapeDtypeStruct((1, 2, 1536, dk), dt)
    v = jax.ShapeDtypeStruct((1, 4, 1536, dv), dt)
    gb = jax.ShapeDtypeStruct((1, 4, 1536), jnp.float32)

    def loss(q, k, v, g, beta):
        return _k_gated_delta_rule(q, k, v, g, beta) \
            .astype(jnp.float32).sum()

    grad = jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))
    text = jax.jit(grad, in_shardings=(one_chip,) * 5) \
        .lower(qk, qk, v, gb, gb).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    assert "custom_call" not in jax.jit(grad).lower(
        qk, qk, v, gb, gb).as_text()


def test_delta_rule_kernels_under_auto_partitioning(four_chips):
    """As the attention kernels: in a step partitioned over the batch
    the rule's kernels run a shard of the batch each
    (`per_batch_shard`), the chunk scan around them is partitioned by
    the compiler."""
    from mxnet_tpu.ops.linear_attention import _k_gated_delta_rule
    from mxnet_tpu.parallel import mesh as mesh_mod

    mesh, batch = four_chips
    qk = jax.ShapeDtypeStruct((4, 2, 256, 128), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((4, 4, 256, 128), jnp.bfloat16)
    gb = jax.ShapeDtypeStruct((4, 4, 256), jnp.float32)

    def loss(q, k, v, g, beta):
        with mesh_mod.auto_partitioned(mesh):
            return _k_gated_delta_rule(q, k, v, g, beta) \
                .astype(jnp.float32).sum()

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)),
                   in_shardings=(batch,) * 5) \
        .lower(qk, qk, v, gb, gb).compile().as_text()
    assert text.count("tpu_custom_call") == 2


def _hybrid_step_aot(topo, monkeypatch, seq=512):
    """A 4-layer hybrid `DecoderLM` (three Gated DeltaNet layers of 4
    key / 8 value heads of 128, one gated attention layer of 16 / 2
    heads of 256, an expert layer in each) at a small width, stepped by
    `DataParallelTrainer(remat=True)` in bf16 on one described chip."""
    config = dict(
        vocab_size=1024, hidden_size=256, head_dim=256,
        num_attention_heads=16, num_key_value_heads=2,
        linear_num_key_heads=4, linear_num_value_heads=8,
        linear_key_head_dim=128, linear_value_head_dim=128,
        linear_conv_kernel_dim=4, num_hidden_layers=4,
        layer_types=["linear_attention"] * 3 + ["full_attention"],
        mlp_layer_types=["sparse"] * 4, moe_intermediate_size=128,
        shared_expert_intermediate_size=128, num_experts=4, router_width=16,
        num_experts_per_tok=2, norm_zero_centered=True, qk_norm=True,
        attention_output_gate="elementwise", shared_expert_gate=True,
        rope_parameters={"full_attention": dict(
            rope_type="default", rope_theta=1e7,
            partial_rotary_factor=0.25)})
    return _step_aot(topo, monkeypatch, config, seq, 1)


def test_hybrid_remat_step_names_its_scopes_and_kernels_aot(topo,
                                                            monkeypatch):
    """The remat step of the hybrid model: the rule's instructions
    carry `/linear_attention/.../delta_rule/` (and the convolution's
    `/conv/`) in forward and backward, the grouped products keep their
    phase prefix (the PR 30 lesson: a change to how layers are traced
    can leave them bare), the one attention layer is three Mosaic
    calls, and the rule's forward runs once a layer outside the
    backward pass's own recomputation: its output is kept by name."""
    import re

    text = _hybrid_step_aot(topo, monkeypatch).as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    rule = [n for n in names if re.search(
        r"/linear_attention/.*/delta_rule/", n)]
    assert any("transpose(jvp(forward))" in n for n in rule)
    assert any("transpose(" not in n for n in rule)
    assert any(re.search(r"/linear_attention/.*/conv/", n) for n in names)
    grouped = [n for n in names if "ragged-dot-" in n]
    assert grouped and all(
        re.match(r"jit\(step_phases\)/.*jvp\(forward\).*/ragged-dot-", n)
        for n in grouped), sorted(set(grouped))
    attention = re.findall(
        r'custom_call_target="tpu_custom_call"[^\n]*attention_full', text)
    assert len(attention) == 3


def _step_aot(topo, monkeypatch, config, seq, chips):
    """`DecoderLM(config)` stepped by `DataParallelTrainer(remat=True)`
    in bf16, two sequences a chip: the whole step compiled for the
    described v5e chips.  Nothing is put on a device: the trainer's
    `global_put` hands back shapes (and plain SGD has no state to make
    there)."""
    import mxnet_tpu as mx
    from mxnet_tpu.models import decoder_lm
    from mxnet_tpu.parallel import data_parallel, mesh as mesh_mod

    net = decoder_lm.DecoderLM(config)
    net.initialize(mx.init.Zero())
    monkeypatch.setattr(
        mesh_mod, "global_put", lambda value, sharding: jax.ShapeDtypeStruct(
            value.shape, value.dtype, sharding=sharding))
    trainer = data_parallel.DataParallelTrainer(
        net, lambda out, _: out, "sgd", {"learning_rate": 1e-3},
        mesh=Mesh(np.array(topo.devices[:chips]), ("dp",)),
        compute_dtype="bfloat16", remat=True)
    ids = np.zeros((2 * chips, seq), np.int32)
    trainer.build((ids, ids))
    scalar = jax.ShapeDtypeStruct((), jnp.float32)
    return trainer._step_fn.lower(
        trainer._params, trainer._states, (ids, ids),
        np.zeros((len(ids),), np.float32),
        jax.ShapeDtypeStruct((2,), jnp.uint32), scalar, scalar).compile()


def _decoder_step_aot(topo, monkeypatch, layer_types, heads, seq, chips,
                      sparse=False):
    """A `DecoderLM` of laguna's head counts (8 K/V heads of 128) at a
    small width and a short sequence."""
    rope = dict(rope_type="default", rope_theta=10000,
                partial_rotary_factor=1)
    config = dict(
        vocab_size=1024, hidden_size=256, head_dim=128,
        num_key_value_heads=8, intermediate_size=512, sliding_window=256,
        num_hidden_layers=len(layer_types), layer_types=layer_types,
        num_attention_heads_per_layer=heads,
        mlp_layer_types=["sparse" if sparse else "dense"] * len(layer_types),
        moe_intermediate_size=128, shared_expert_intermediate_size=128,
        num_experts=4, router_width=16, num_experts_per_tok=2,
        rope_parameters={"full_attention": rope, "sliding_attention": rope})
    return _step_aot(topo, monkeypatch, config, seq, chips)


@pytest.mark.parametrize("layer_types,heads,chips", [
    (["full_attention"] * 2, [48, 48], 1),
    (["sliding_attention"] * 2, [64, 64], 1),
    (["full_attention", "sliding_attention"], [48, 64], 1),
    (["full_attention", "sliding_attention"], [48, 64], 4),
], ids=["full", "window", "mixed", "mixed-dp4"])
def test_remat_step_runs_the_forward_kernel_once_a_layer_aot(
        topo, monkeypatch, layer_types, heads, chips):
    """`DataParallelTrainer(remat=True)` keeps the flash kernels' named
    output and row statistic: the compiled step holds three attention
    kernels a layer (forward, dQ, dK/dV), four under a bare
    `jax.checkpoint`, whatever the layer's kind, and on four chips too,
    where the kernels and their names sit inside `per_batch_shard`'s
    `shard_map`; and a chip's temporaries grow by no more than the
    bytes named there, plus a tenth."""
    from mxnet_tpu.parallel import data_parallel

    seq = 512
    args = topo, monkeypatch, layer_types, heads, seq, chips
    kept = _decoder_step_aot(*args)
    monkeypatch.setattr(data_parallel, "_remat_policy", lambda: None)
    bare = _decoder_step_aot(*args)
    assert kept.as_text().count("tpu_custom_call") == 3 * len(heads)
    assert bare.as_text().count("tpu_custom_call") == 4 * len(heads)
    # a layer keeps b*s*h*d x itemsize + 4*b*h*s bytes
    named = sum(2 * seq * h * (128 * 2 + 4) for h in heads)
    grown = kept.memory_analysis().temp_size_in_bytes \
        - bare.memory_analysis().temp_size_in_bytes
    assert 0 < grown <= 1.1 * named, (grown, named)


def test_remat_step_names_the_grouped_products_by_phase_aot(topo,
                                                           monkeypatch):
    """The TPU compiler names the expert layer's grouped products
    `<the call's op_name>/ragged-dot-...` only where the function that
    holds them is SHARED by the layers (one it can inline early leaves
    a bare `ragged-dot-none`, and the device trace's readers find no
    phase).  The layers share their lowered functions as long as every
    checkpoint has the SAME policy object: JAX caches the partial
    evaluation by its identity."""
    import re

    from mxnet_tpu.parallel import data_parallel

    assert data_parallel._remat_policy() is data_parallel._remat_policy()
    text = _decoder_step_aot(
        topo, monkeypatch, ["sliding_attention"] * 2, [64, 64], 512, 1,
        sparse=True).as_text()
    names = re.findall(r'op_name="([^"]*ragged-dot-[^"]*)"', text)
    assert names and all(
        re.match(r"jit\(step_phases\)/.*jvp\(forward\).*/ragged-dot-", name)
        for name in names), sorted(set(names))


@pytest.mark.parametrize("rotated", [128, 64], ids=["whole", "half"])
def test_rotary_embedding_float32_aot(one_chip, rotated):
    """The rotary op in float32 at the cell's head shapes (what the
    trainer's eager probe runs).  Written as a concatenate of two
    64-wide float32 pieces it aborted the TPU compiler
    (`IsFusibleUnalignedDUS`); the op is a reshape and a reverse."""
    from mxnet_tpu.ops.nn import _k_rotary_embedding, rotary_frequencies

    inv_freq, _ = rotary_frequencies(rotated, rope_theta=10000.0)
    spec = jax.ShapeDtypeStruct((2, 8, 8192, 128), jnp.float32)

    def loss(x):
        return _k_rotary_embedding(x, inv_freq=inv_freq,
                                   attention_factor=1.4).sum()

    jax.jit(jax.value_and_grad(loss), in_shardings=(one_chip,)) \
        .lower(spec).compile()


def test_expert_layer_at_the_laguna_cells_shapes_aot(one_chip):
    """`moe_ffn` at the cell's size: 16,384 tokens, a 256-wide router,
    16 held experts of width 512, top-8: the grouped products compile
    to the TPU's ragged-dot kernels, forward and backward, once for
    each row capacity inside a conditional a pass, and no pass scatters
    rows."""
    import re

    from mxnet_tpu.ops.moe import _k_moe_ffn, capacities

    bf16 = jnp.bfloat16
    specs = (jax.ShapeDtypeStruct((16384, 2048), bf16),
             jax.ShapeDtypeStruct((2048, 256), bf16),
             jax.ShapeDtypeStruct((16, 2048, 1024), bf16),
             jax.ShapeDtypeStruct((16, 512, 2048), bf16))

    def loss(x, router, w_in, w_out):
        return _k_moe_ffn(x, router, w_in, w_out, first_expert=0, top_k=8,
                          scale=2.5)[0].astype(jnp.float32).sum()

    text = _aot_grad_compile(one_chip, loss, *specs)
    caps = capacities(16384 * 8)
    assert caps == (16384, 32768, 131072)
    branches = re.findall(r"conditional\(.*branch_computations=\{([^}]*)\}",
                          text)
    assert [len(b.split(",")) for b in branches] == [len(caps)] * 2
    products = re.findall(r"= (\w+)\[(\d+),\d+\]\S* custom-call\(.*"
                          r'op_name="[^"]*ragged-dot-none"', text)
    rows = [int(n) for _, n in products]
    # by capacity: a pair forward; backward the pair again and, for the
    # gradient of x alone, each product's transpose to its rows
    assert sorted(rows) == sorted(caps * 6), sorted(rows)
    assert len(re.findall(r'op_name="[^"]*ragged-dot-none"', text)) \
        == len(rows)
    assert not re.search(r"\[\d+,2048\]\S* scatter\(", text)
    assert not re.search(r"\[16,\d+,\d+\]\S* scatter\(", text)


def _dispatch_loss(q, mask):
    from mxnet_tpu.ops.attention import _k_sdpa

    return _k_sdpa(q, q, q, mask).astype(jnp.float32).sum()


def test_attention_dispatch_lowers_kernel_for_tpu(one_chip):
    """ops/attention picks its branch by the platform it is LOWERED
    for: compiled for the described chip from a CPU process, BERT's
    attention (with the (b,1,1,sk) mask BERTModel builds) is the
    kernel; lowered for the CPU it is the XLA form."""
    q = jax.ShapeDtypeStruct(BERT_ATTN, jnp.bfloat16)
    mask = jax.ShapeDtypeStruct((64, 1, 1, 128), jnp.float32)
    f = jax.grad(_dispatch_loss)
    text = jax.jit(f, in_shardings=(one_chip, one_chip),
                   out_shardings=one_chip).lower(q, mask) \
        .compile().as_text()
    assert text.count("tpu_custom_call") == 3
    assert "custom_call" not in jax.jit(f).lower(q, mask).as_text()


def test_attention_kernel_under_auto_partitioning(four_chips):
    """DataParallelTrainer lowers through jit with shardings; the
    compiler refuses to partition a bare Mosaic call, so the step
    declares its mesh (parallel.mesh.auto_partitioned) and the call
    site shards the kernel over the batch axis."""
    from mxnet_tpu.parallel import mesh as mesh_mod

    mesh, batch = four_chips
    q = jax.ShapeDtypeStruct(BERT_ATTN, jnp.bfloat16)
    mask = jax.ShapeDtypeStruct((64, 1, 1, 128), jnp.float32)

    def lowered(loss):
        return jax.jit(jax.grad(loss), in_shardings=(batch, batch),
                       out_shardings=batch).lower(q, mask)

    def declared(q, mask):
        with mesh_mod.auto_partitioned(mesh):
            return _dispatch_loss(q, mask)

    assert lowered(declared).compile().as_text().count(
        "tpu_custom_call") == 3
    with pytest.raises(NotImplementedError,
                       match="cannot be automatically partitioned"):
        lowered(_dispatch_loss).compile()


def test_attention_kernel_inside_replica_shard_map(four_chips):
    """gluon.Trainer over several contexts traces the model inside a
    shard_map with the varying-axes check on; the kernels' outputs are
    typed varying like their operands (ops/pallas.pallas_call)."""
    mesh, batch = four_chips
    P = PartitionSpec
    q = jax.ShapeDtypeStruct(BERT_ATTN, jnp.float32)
    mask = jax.ShapeDtypeStruct((64, 1, 1, 128), jnp.float32)

    def replica(q, mask):
        loss, grad = jax.value_and_grad(_dispatch_loss)(q, mask)
        return jax.lax.psum(loss, "dp"), grad

    text = jax.jit(
        jax.shard_map(replica, mesh=mesh, in_specs=(P("dp"), P("dp")),
                      out_specs=(P(), P("dp"))),
        in_shardings=(batch, batch)).lower(q, mask).compile().as_text()
    assert text.count("tpu_custom_call") == 3 and "all-reduce" in text


def test_bert_base_layer_step_aot(one_chip):
    """One BERT-base encoder layer (768 / 3072 / 12 heads) at batch 64
    x seq 128 in bf16, value-and-grad through the gluon block: the
    kernel is in the layer the trainer compiles, not only beside it."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.gluon.block import CachedOp
    from mxnet_tpu.models.bert import BERTEncoderLayer

    mx.random.seed(0)
    net = BERTEncoderLayer(dropout=0.0)
    net.initialize(mx.init.Xavier())
    net(nd.ones((2, 128, 768)), nd.zeros((2, 1, 1, 128)))
    fn = CachedOp(net)._build_fn(True)
    raws = [jax.ShapeDtypeStruct(p.shape, jnp.bfloat16)
            for _, p in net._ordered_params()]
    x = jax.ShapeDtypeStruct((64, 128, 768), jnp.bfloat16)
    mask = jax.ShapeDtypeStruct((64, 1, 1, 128), jnp.float32)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)

    def loss(x, key, mask, *raws):
        out = fn(key, *raws, x, mask, _n_params=len(raws))
        return out[0].astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss), in_shardings=one_chip,
                   out_shardings=one_chip).lower(
                       x, key, mask, *raws).compile().as_text()
    assert text.count("tpu_custom_call") == 3
