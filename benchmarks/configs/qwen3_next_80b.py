"""The `qwen3_next_80b` configuration through the program: the decoder
of mxnet_tpu/models/decoder_lm.py (three Gated DeltaNet layers to one
gated attention layer, an expert layer with a gated shared expert in
each), next-token loss, under `parallel.DataParallelTrainer` with
recomputation of each layer, built from the published keys in
qwen3_next_80b.json.  mxnet_tpu is imported only inside `build`."""
from __future__ import annotations

import numpy as np

from harness import work

BYTES_PER_ELEMENT = 2


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


def layer_types(config):
    """The mixer of each layer held, from the published interval: every
    `full_attention_interval`-th layer is softmax attention, the rest
    Gated DeltaNet."""
    every = config["full_attention_interval"]
    return ["full_attention" if (i + 1) % every == 0 else "linear_attention"
            for i in range(config["num_hidden_layers"])]


def mlp_layer_types(config):
    """`sparse` or `dense` a layer, from `decoder_sparse_step` and
    `mlp_only_layers` as HF's Qwen3NextDecoderLayer reads them."""
    step, dense = config["decoder_sparse_step"], config["mlp_only_layers"]
    return ["sparse" if i not in dense and config["num_experts"] > 0
            and (i + 1) % step == 0 else "dense"
            for i in range(config["num_hidden_layers"])]


def decoder_config(config):
    """The published keys as `DecoderLM` reads a model: the per-layer
    lists derived, the rotary parameters by layer type, and the
    family's switches (zero-centred norms, q/k norms, the output gate
    an element, a gate on the shared expert)."""
    return dict(
        config, layer_types=layer_types(config),
        mlp_layer_types=mlp_layer_types(config),
        rope_parameters={"full_attention": {
            "rope_type": "default", "rope_theta": config["rope_theta"],
            "partial_rotary_factor": config["partial_rotary_factor"]}},
        norm_zero_centered=True, qk_norm=True,
        attention_output_gate="elementwise", shared_expert_gate=True,
        moe_routed_scaling_factor=1.0)


def _count(config, kind):
    return layer_types(config).count(kind)


def linear_sizes(config):
    """(key heads, value heads, key size, value size)."""
    return (config["linear_num_key_heads"], config["linear_num_value_heads"],
            config["linear_key_head_dim"], config["linear_value_head_dim"])


def visible_pairs(seq):
    """(query, key) pairs one causal head of one sequence sees."""
    return seq * (seq + 1) // 2


def expected_expert_rows(config, traffic):
    """Rows the held experts of one layer get when the router spreads
    its assignments evenly over its whole width."""
    return (units_per_step(config, traffic) * config["num_experts_per_tok"]
            * config["num_experts"] / config["router_width"])


def model_flops_per_step(config, traffic):
    """FLOPs one training step needs by the published sizes: matrix
    products of the forward pass times 3 (forward, and the backward's
    two products for each); attention over the visible pairs only; the
    delta rule in its recurrent count (three products of key size x
    value size a token and value head); the routed experts at their
    expected rows; nothing recomputed; element-wise work, norms, the
    short convolution, rotary embedding and lookups not counted."""
    h, d = config["hidden_size"], config["head_dim"]
    n, kv = config["num_attention_heads"], config["num_key_value_heads"]
    hk, hv, dk, dv = linear_sizes(config)
    b, s = traffic["batch"], traffic["seq_len"]
    tokens = b * s
    forward = 2 * tokens * h * config["vocab_size"]          # head
    # [q | gate], k, v and the output projection; the attention itself
    full = 2 * tokens * h * (3 * n * d + 2 * kv * d) \
        + 4 * b * n * visible_pairs(s) * d
    # [q | k | v | z], [b | a], the output projection; the rule
    linear = 2 * tokens * h * (2 * hk * dk + 3 * hv * dv + 2 * hv) \
        + 6 * tokens * hv * dk * dv
    forward += _count(config, "full_attention") * full \
        + _count(config, "linear_attention") * linear
    sparse = mlp_layer_types(config).count("sparse")
    forward += sparse * (
        2 * tokens * h * config["router_width"]
        + tokens * h * (6 * config["shared_expert_intermediate_size"] + 2)
        + 6 * expected_expert_rows(config, traffic) * h
        * config["moe_intermediate_size"])
    return 3 * forward


def attention_work(config, traffic):
    """(FLOPs, bytes) a step's softmax attention needs in the full
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


def projection_work(config, traffic):
    """(FLOPs, bytes) of every dense product of every mixer held, by
    the published shapes, whatever implements them (`harness/work.py:
    dense_work`): the
    attention layer's `[q | gate]` (doubled: the gate is an element's),
    k, v and output projection; the linear mixer's `[q | k | v | z]`,
    `[b | a]` and output projection.  Norms, rotary embedding, l2
    norms, the gates' own arithmetic, the short convolution and the
    rule are not counted."""
    h, d = config["hidden_size"], config["head_dim"]
    n, kv = config["num_attention_heads"], config["num_key_value_heads"]
    hk, hv, dk, dv = linear_sizes(config)
    full = [(h, 2 * n * d), (h, kv * d), (h, kv * d), (n * d, h)]
    linear = [(h, 2 * hk * dk + 2 * hv * dv), (h, 2 * hv), (hv * dv, h)]
    return work.dense_work(
        units_per_step(config, traffic),
        _count(config, "full_attention") * full
        + _count(config, "linear_attention") * linear)


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
    of each such layer got: 6 h w a row forward, twice that backward;
    the held experts' weights and the rows in and out, once a pass."""
    h, w = config["hidden_size"], config["moe_intermediate_size"]
    flops = moved = 0
    for n in rows:
        flops += 3 * 6 * n * h * w
        moved += 3 * BYTES_PER_ELEMENT * (
            config["num_experts"] * 3 * h * w + n * (2 * h + 3 * w))
    return flops, moved


def delta_rule_work(config, traffic):
    """(FLOPs, bytes) a step's gated delta rule needs in the linear
    layers held, by shapes, whatever implements it, forward and
    backward.
      FLOPs: the recurrent form's three products a token and value
             head (S'^T k, k d^T, S^T q: 6 x key size x value size
             forward), twice that backward; nothing recomputed.
      bytes: forward reads q, k (at the key heads), v (bf16), g, beta
             (float32) and writes o; backward reads them and do again
             and writes dq, dk, dv, dg, dbeta."""
    hk, hv, dk, dv = linear_sizes(config)
    tokens = units_per_step(config, traffic)
    layers = _count(config, "linear_attention")
    operands = tokens * (BYTES_PER_ELEMENT * (2 * hk * dk + hv * dv)
                         + 2 * 4 * hv)
    out = tokens * BYTES_PER_ELEMENT * hv * dv
    return (layers * 3 * 6 * tokens * hv * dk * dv,
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
