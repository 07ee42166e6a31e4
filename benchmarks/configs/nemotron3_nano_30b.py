"""The `nemotron3_nano_30b` configuration through the program: the decoder
of mxnet_tpu/models/decoder_lm.py in layers of ONE part each (Mamba-2
mixers, a GQA attention layer with no rotary embedding, expert layers
of sigmoid-routed relu^2 experts with a shared expert), next-token
loss, under `parallel.DataParallelTrainer` with recomputation of each
layer, built from the published keys in nemotron3_nano_30b.json.
mxnet_tpu is imported only inside `build`."""
from __future__ import annotations

import numpy as np

BYTES_PER_ELEMENT = 2
KINDS = {"M": ("mamba", None), "*": ("full_attention", None),
         "E": (None, "sparse")}


def make_batch(rng, config, traffic):
    """One seeded batch: `batch` documents of `seq_len` + 1 token ids
    drawn Zipf(1.0) over the vocabulary rows held (so rows repeat and
    the routing is uneven, as text's is); the labels are the ids
    shifted by one.  (x, y) for `trainer.step(x, y)`."""
    bs, seq_len, vocab = traffic["batch"], traffic["seq_len"], config[
        "vocab_size"]
    p = 1.0 / np.arange(1, vocab + 1)
    tokens = rng.choice(vocab, size=(bs, seq_len + 1), p=p / p.sum())
    x = (tokens[:, :-1].astype(np.int32), tokens[:, 1:].astype(np.int32))
    return x, np.zeros((bs,), np.float32)


def reference_batch(x, y):
    """What the reference's `follow` takes for this batch."""
    del y
    return x


def units_per_step(config, traffic):
    """Tokens a step trains on."""
    return traffic["batch"] * traffic["seq_len"]


def _pattern(config):
    pattern = config["hybrid_override_pattern"]
    if len(pattern) != config["num_hidden_layers"] or set(pattern) - set(
            KINDS):
        raise ValueError(f"hybrid_override_pattern {pattern!r}: one of "
                         f"{sorted(KINDS)} a layer held")
    return pattern


def layer_types(config):
    """The mixer of each layer held, None where it has none, from
    `hybrid_override_pattern` as HF's NemotronH reads it: M a Mamba-2
    mixer, * attention, E an expert layer alone."""
    return [KINDS[c][0] for c in _pattern(config)]


def mlp_layer_types(config):
    """`sparse` where the layer is an expert layer, None elsewhere."""
    return [KINDS[c][1] for c in _pattern(config)]


def decoder_config(config):
    """The published keys as `DecoderLM` reads a model: the per-layer
    lists derived from the pattern, the expert layer's names (held
    experts, the shared expert's width, scaling), the norms' eps, and
    the family's switches (sigmoid router with a correction bias,
    relu^2 experts, no output gate, no rotary embedding)."""
    return dict(
        config, layer_types=layer_types(config),
        mlp_layer_types=mlp_layer_types(config),
        num_experts=config["n_routed_experts"],
        shared_expert_intermediate_size=config[
            "moe_shared_expert_intermediate_size"],
        moe_routed_scaling_factor=config["routed_scaling_factor"],
        rms_norm_eps=config["layer_norm_epsilon"], rope_parameters={},
        attention_output_gate=None, router_scoring="sigmoid",
        expert_activation="relu2")


def _count(config, kind):
    return layer_types(config).count(kind)


def mamba_sizes(config):
    """(heads, head size, groups, state size)."""
    return (config["mamba_num_heads"], config["mamba_head_dim"],
            config["n_groups"], config["ssm_state_size"])


def visible_pairs(seq):
    """(query, key) pairs one causal head of one sequence sees."""
    return seq * (seq + 1) // 2


def expected_expert_rows(config, traffic):
    """Rows the held experts of one layer get when the router spreads
    its assignments evenly over its whole width."""
    return (units_per_step(config, traffic) * config["num_experts_per_tok"]
            * config["n_routed_experts"] / config["router_width"])


def model_flops_per_step(config, traffic):
    """FLOPs one training step needs by the published sizes: matrix
    products of the forward pass times 3 (forward, and the backward's
    two products for each); attention over the visible pairs only; the
    SSD in its recurrent count (two products of head size x state size
    a token and head: dt x B^T and S C); the routed experts at their
    expected rows; nothing recomputed; element-wise work, norms, the
    short convolution and lookups not counted."""
    h, d = config["hidden_size"], config["head_dim"]
    n, kv = config["num_attention_heads"], config["num_key_value_heads"]
    heads, size, groups, state = mamba_sizes(config)
    b, s = traffic["batch"], traffic["seq_len"]
    tokens = b * s
    inner = heads * size
    forward = 2 * tokens * h * config["vocab_size"]          # head
    full = 2 * tokens * h * (2 * n * d + 2 * kv * d) \
        + 4 * b * n * visible_pairs(s) * d
    # [x | B | C | z], dt, the output projection; the scan
    mamba = 2 * tokens * h * (2 * inner + 2 * groups * state + heads
                              + inner) + 4 * tokens * heads * size * state
    width = config["moe_intermediate_size"]
    shared = config["moe_shared_expert_intermediate_size"]
    sparse = mlp_layer_types(config).count("sparse")
    forward += _count(config, "full_attention") * full \
        + _count(config, "mamba") * mamba + sparse * (
            2 * tokens * h * config["router_width"]
            + 4 * tokens * h * shared
            + 4 * expected_expert_rows(config, traffic) * h * width)
    return 3 * forward


def attention_work(config, traffic):
    """(FLOPs, bytes) a step's softmax attention needs in the attention
    layers held, by shapes, whatever implements it, forward and
    backward, bf16.
      FLOPs: QK^T and PV forward (4 a pair and head-size element); dV,
             dP, dQ, dK backward (8); no recomputation counted.
      bytes: forward reads q, k, v and writes o; backward reads q, k,
             v, o, do and writes dq, dk, dv; K/V at their own (fewer)
             heads."""
    d = config["head_dim"]
    n, kv = config["num_attention_heads"], config["num_key_value_heads"]
    b, s = traffic["batch"], traffic["seq_len"]
    layers = _count(config, "full_attention")
    return (layers * 12 * b * n * visible_pairs(s) * d,
            layers * (6 * n + 6 * kv) * b * s * d * BYTES_PER_ELEMENT)


def attention_kernel_events(config, traffic):
    """How the attention kernels' device events are named in the trace
    (harness.trace.short_name): Mosaic calls whose first operand is the
    (batch * kv heads, group, seq, head size) query."""
    n, kv = config["num_attention_heads"], config["num_key_value_heads"]
    return (rf"tpu_custom_call\(bf16\[{traffic['batch'] * kv},{n // kv},"
            rf"{traffic['seq_len']},{config['head_dim']}\]\)")


def expert_work(config, rows):
    """(FLOPs, bytes) of the grouped products of every expert layer
    held, forward and backward, for `rows` = the rows the held experts
    of each such layer got: relu^2 experts, two products of h x w (4 h
    w a row forward, twice that backward); the held experts' weights
    and the rows in and out, once a pass."""
    h, w = config["hidden_size"], config["moe_intermediate_size"]
    flops = moved = 0
    for n in rows:
        flops += 3 * 4 * n * h * w
        moved += 3 * BYTES_PER_ELEMENT * (
            config["n_routed_experts"] * 2 * h * w + n * (2 * h + 2 * w))
    return flops, moved


def ssd_work(config, traffic):
    """(FLOPs, bytes) a step's SSD scan needs in the Mamba-2 layers held,
    by shapes, whatever implements it, forward and backward.
      FLOPs: the recurrent form's two products a token and head (dt x
             B^T into the state, S C out of it: 4 x head size x state
             size forward), twice that backward; nothing recomputed.
      bytes: forward reads x, B, C (bf16) and dt (float32) and writes
             y; backward reads them and dy again and writes dx, dB, dC
             and ddt."""
    heads, size, groups, state = mamba_sizes(config)
    tokens = units_per_step(config, traffic)
    layers = _count(config, "mamba")
    operands = tokens * (BYTES_PER_ELEMENT * (heads * size
                                              + 2 * groups * state)
                         + 4 * heads)
    out = tokens * BYTES_PER_ELEMENT * heads * size
    return (layers * 3 * 4 * tokens * heads * size * state,
            layers * (3 * operands + 2 * out))


def build(config, traffic, weights):
    """The trainer whose `step` the window drives."""
    del traffic
    import mxnet_tpu as mx
    from mxnet_tpu.models import decoder_lm
    from mxnet_tpu.parallel import data_parallel

    from harness import gluon_program

    # the block lives on the host: the trainer puts its own copy of the
    # parameters on the chip
    ctx = mx.cpu()
    net = decoder_lm.DecoderLM(decoder_config(config))
    net.initialize(mx.init.Zero(), ctx=ctx)
    gluon_program.fill(net, weights, ctx)
    optimizer = dict(config["assumed"]["optimizer"])
    return data_parallel.DataParallelTrainer(
        net, lambda out, _label: out, optimizer.pop("name"), optimizer,
        compute_dtype=config["assumed"]["compute_dtype"],
        remat=config["assumed"]["remat"])
