"""The `laguna_xs2` configuration through the program: the decoder of
mxnet_tpu/models/decoder_lm.py, next-token loss, under
`parallel.DataParallelTrainer` with recomputation of each layer, built
from the sizes in laguna_xs2.json.  mxnet_tpu is imported only inside
`build`."""
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


def layers(config):
    """[(query heads, window or None, sparse)] of the layers held."""
    return [(config["num_attention_heads_per_layer"][i],
             config["sliding_window"]
             if config["layer_types"][i] == "sliding_attention" else None,
             config["mlp_layer_types"][i] == "sparse")
            for i in range(config["num_hidden_layers"])]


def visible_pairs(seq, window):
    """(query, key) pairs one head of one sequence sees."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def expected_expert_rows(config, traffic):
    """Rows the held experts of one layer get when the router spreads
    its assignments evenly over its whole width."""
    return (units_per_step(config, traffic) * config["num_experts_per_tok"]
            * config["num_experts"] / config["router_width"])


def model_flops_per_step(config, traffic):
    """FLOPs one training step needs by the published sizes: matrix
    products of the forward pass times 3 (forward, and the backward's
    two products for each); attention over the visible pairs only; the
    routed experts at their expected rows; nothing recomputed;
    element-wise work, norms, rotary embedding and lookups not
    counted."""
    h, d, kv = (config["hidden_size"], config["head_dim"],
                config["num_key_value_heads"])
    b, s = traffic["batch"], traffic["seq_len"]
    tokens = b * s
    forward = 2 * tokens * h * config["vocab_size"]          # head
    for heads, window, sparse in layers(config):
        forward += 2 * tokens * h * (2 * heads * d + 2 * kv * d + heads)
        forward += 4 * b * heads * visible_pairs(s, window) * d
        if sparse:
            forward += 2 * tokens * h * config["router_width"]
            forward += 6 * tokens * h * config[
                "shared_expert_intermediate_size"]
            forward += 6 * expected_expert_rows(config, traffic) * h \
                * config["moe_intermediate_size"]
        else:
            forward += 6 * tokens * h * config["intermediate_size"]
    return 3 * forward


def attention_work(config, traffic):
    """(FLOPs, bytes) a step's attention needs in every layer held, by
    shapes, whatever implements it, forward and backward, bf16.
      FLOPs: QK^T and PV forward (4 a pair and head-size element); dV,
             dP, dQ, dK backward (8); no recomputation counted.
      bytes: forward reads q, k, v and writes o; backward reads q, k,
             v, o, do and writes dq, dk, dv; K/V at their own (fewer)
             heads."""
    d, kv = config["head_dim"], config["num_key_value_heads"]
    b, s = traffic["batch"], traffic["seq_len"]
    flops = moved = 0
    for heads, window, _ in layers(config):
        flops += 12 * b * heads * visible_pairs(s, window) * d
        moved += (6 * heads + 6 * kv) * b * s * d * BYTES_PER_ELEMENT
    return flops, moved


def projection_work(config, traffic):
    """(FLOPs, bytes) of every dense product of every mixer held, by
    the published shapes, whatever implements them: q, the shared k and
    v, the per-head output gate and the output projection of each
    attention layer at its own head count (`harness/work.py:
    dense_work`).  Norms, rotary embedding, the gate's sigmoid and the
    head transposes are not counted."""
    h, d = config["hidden_size"], config["head_dim"]
    kv = config["num_key_value_heads"]
    products = []
    for heads, _, _ in layers(config):
        products += [(h, heads * d), (h, kv * d), (h, kv * d), (h, heads),
                     (heads * d, h)]
    return work.dense_work(units_per_step(config, traffic), products)


def attention_kernel_events(config, traffic):
    """How the attention kernels' device events are named in the trace
    (harness.trace.short_name): Mosaic calls whose first operand is the
    (batch * kv heads, group, seq, head size) query."""
    d, kv = config["head_dim"], config["num_key_value_heads"]
    groups = sorted({heads // kv for heads, _, _ in layers(config)})
    return (rf"tpu_custom_call\(bf16\[{traffic['batch'] * kv},"
            rf"({'|'.join(map(str, groups))}),{traffic['seq_len']},{d}\]\)")


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


def build(config, traffic, weights):
    """The trainer whose `step` the window drives."""
    del traffic
    import mxnet_tpu as mx
    from mxnet_tpu.models import decoder_lm
    from mxnet_tpu.parallel import data_parallel

    from harness import gluon_program

    # the block lives on the host: the trainer puts its own copy of the
    # parameters on the chip, and a second 2 GB of weights beside 2 GB of
    # Gluon gradient buffers that nothing reads would crowd the step
    ctx = mx.cpu()
    net = decoder_lm.DecoderLM(config)
    net.initialize(mx.init.Zero(), ctx=ctx)
    gluon_program.fill(net, weights, ctx)
    optimizer = dict(config["assumed"]["optimizer"])
    return data_parallel.DataParallelTrainer(
        net, lambda out, _label: out, optimizer.pop("name"), optimizer,
        compute_dtype=config["assumed"]["compute_dtype"],
        remat=config["assumed"]["remat"])
