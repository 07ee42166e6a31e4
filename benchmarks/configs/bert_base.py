"""The `bert_base` configuration through the program: BERT pre-training
(MLM + NSP) under `parallel.DataParallelTrainer`, built from the sizes
in bert_base.json.  mxnet_tpu is imported only inside `build`."""
from __future__ import annotations

import numpy as np


def masked_per_sequence(config, traffic):
    return max(1, int(round(traffic["seq_len"] * config["masked_lm_prob"])))


def make_batch(rng, config, traffic):
    """One seeded pre-training batch, as examples/bert/pretrain_bert.py's
    `synthetic_batch` makes it: (x, y) for `trainer.step(x, y)`."""
    bs, seq_len, vocab = traffic["batch"], traffic["seq_len"], config[
        "vocab_size"]
    k = masked_per_sequence(config, traffic)
    tokens = rng.randint(4, vocab, (bs, seq_len))
    types = np.zeros((bs, seq_len), np.int32)
    types[:, seq_len // 2:] = 1
    positions = np.stack([rng.choice(seq_len, k, replace=False)
                          for _ in range(bs)]).astype(np.int32)
    targets = np.take_along_axis(tokens, positions, 1)
    inputs = tokens.copy()
    np.put_along_axis(inputs, positions, 3, 1)  # 3 = [MASK]
    weights = np.ones((bs, k), np.float32)
    nsp = rng.randint(0, 2, (bs,))
    valid = np.full((bs,), seq_len, np.int32)
    x = (inputs.astype(np.int32), types, targets.astype(np.int32),
         nsp.astype(np.int32), weights, valid, positions)
    return x, np.zeros((bs,), np.float32)


def reference_batch(x, y):
    """What the reference's `follow` takes for this batch."""
    del y
    return x


def units_per_step(config, traffic):
    """Tokens a step trains on."""
    return traffic["batch"] * traffic["seq_len"]


def model_flops_per_step(config, traffic):
    """FLOPs one training step needs by the published sizes: matrix
    products of the forward pass times 3 (forward, and the backward's
    two products for each), the MLM head at the masked positions only,
    nothing recomputed, element-wise work and lookups not counted."""
    h, ffn = config["hidden_size"], config["intermediate_size"]
    layers, vocab = config["num_hidden_layers"], config["vocab_size"]
    b, s = traffic["batch"], traffic["seq_len"]
    tokens, masked = b * s, b * masked_per_sequence(config, traffic)
    per_layer = 2 * tokens * (4 * h * h + 2 * h * ffn)  # qkv, out, ffn
    attention = 4 * b * s * s * h                       # QK^T and PV
    heads = (2 * b * h * h                              # pooler
             + 2 * b * h * 2                            # NSP classifier
             + 2 * masked * h * h                       # MLM transform
             + 2 * masked * h * vocab)                  # MLM decoder
    return 3 * (layers * (per_layer + attention) + heads)


def attention_shape(config, traffic):
    """(batch, heads, seq, head_dim, layers) of the self-attention."""
    heads = config["num_attention_heads"]
    return (traffic["batch"], heads, traffic["seq_len"],
            config["hidden_size"] // heads, config["num_hidden_layers"])


def build(config, traffic, weights):
    """The trainer whose `step` the window drives."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon import HybridBlock
    from mxnet_tpu.models import bert
    from mxnet_tpu.parallel import data_parallel

    from harness import gluon_program

    class BERTForPretrain(HybridBlock):
        """MLM + NSP loss head over the backbone, one scalar loss out
        (copy of examples/bert/pretrain_bert.py's)."""

        def __init__(self, model, **kwargs):
            super().__init__(**kwargs)
            self.model = model

        def hybrid_forward(self, F, inputs, token_types, mlm_targets,
                           nsp_labels, mask_weight, valid_length,
                           masked_positions):
            mlm_scores, nsp_scores = self.model(
                inputs, token_types, valid_length, masked_positions)
            mlm_ll = F.pick(F.log_softmax(mlm_scores), mlm_targets, axis=-1)
            mlm_loss = -F.sum(mlm_ll * mask_weight) \
                / (F.sum(mask_weight) + 1)
            nsp_ll = F.pick(F.log_softmax(nsp_scores), nsp_labels, axis=-1)
            return mlm_loss - F.mean(nsp_ll)

    ctx = mx.xla(0)
    net = BERTForPretrain(bert.BERTModel(
        config["vocab_size"], config["hidden_size"],
        config["intermediate_size"], config["num_hidden_layers"],
        config["num_attention_heads"],
        max_length=config["max_position_embeddings"],
        type_vocab_size=config["type_vocab_size"],
        dropout=config["hidden_dropout_prob"]))
    net.initialize(mx.init.Zero(), ctx=ctx)
    gluon_program.fill(net, weights, ctx)
    optimizer = dict(config["assumed"]["optimizer"])
    return data_parallel.DataParallelTrainer(
        net, lambda out, _label: out, optimizer.pop("name"), optimizer,
        compute_dtype=config["assumed"]["compute_dtype"])
