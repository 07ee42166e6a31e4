"""Plain reference for the `bert_base` configuration: BERT pre-training
(MLM + NSP), forward, loss, gradients and AdamW in straightforward
float32 `jax.numpy`.  Imports nothing of mxnet_tpu (only the
benchmark's own rounding helper for the control).

Follows Devlin et al. 2018 (arXiv:1810.04805) section 3 with the
departures the program's model makes, each listed in the
configuration's `assumed`: tanh-approximated GELU, LayerNorm eps 1e-5,
untied MLM decoder, the MLM loss normalised by (masked count + 1),
weight decay on every leaf, MLM head decoded at the masked positions
only.

Parameters are a flat list in the order the program's
`block._ordered_params()` gives them (names there carry process-wide
counters, so the order and the shapes are the contract, not the names).

Dropout: mask i of a step is `bernoulli(fold_in(step_key, i), 1 - p)`
over the whole batch's activation shape, i counting the dropout sites
in forward order (embeddings, then attention output and FFN output of
each layer), and step t's key is `fold_in(PRNGKey(seed), t)`, t from 0:
mxnet_tpu.random's documented stream after `mx.random.seed(seed)`.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from harness import lowprec

LEAVES_PER_LAYER = 12


def param_specs(config):
    """[(shape, kind, scale)] in program order."""
    h, ffn = config["hidden_size"], config["intermediate_size"]
    vocab, std = config["vocab_size"], config["assumed"]["init_stdev"]
    w = lambda *shape: (shape, "trunc_normal", std)  # noqa: E731
    zeros = lambda n: ((n,), "zeros", 0.0)  # noqa: E731
    ones = lambda n: ((n,), "ones", 0.0)  # noqa: E731
    specs = [w(vocab, h), w(config["type_vocab_size"], h),
             w(config["max_position_embeddings"], h), ones(h), zeros(h)]
    for _ in range(config["num_hidden_layers"]):
        specs += [w(3 * h, h), zeros(3 * h), w(h, h), zeros(h),
                  ones(h), zeros(h), w(ffn, h), zeros(ffn),
                  w(h, ffn), zeros(h), ones(h), zeros(h)]
    specs += [w(h, h), zeros(h), w(h, h), zeros(h), ones(h), zeros(h),
              w(vocab, h), zeros(vocab), w(2, h), zeros(2)]
    return specs


def leaf_parts(config):
    """How many of the published model's tensors each leaf packs along
    its first axis: the comparison takes norms tensor by tensor.  The
    program keeps the query, key and value projections in one leaf."""
    packed = [3, 3] + [1] * (LEAVES_PER_LAYER - 2)
    return [1] * 5 + packed * config["num_hidden_layers"] + [1] * 10


def _part_norms(leaves, parts):
    return jnp.stack([
        jnp.linalg.norm(chunk.ravel()) for x, k in zip(leaves, parts)
        for chunk in (jnp.split(x, k, axis=0) if k > 1 else [x])])


def _structure(flat, n_layers):
    """Flat program-order list -> (embed leaves, stacked layer leaves,
    head leaves)."""
    embed = tuple(flat[:5])
    per = [flat[5 + i * LEAVES_PER_LAYER:5 + (i + 1) * LEAVES_PER_LAYER]
           for i in range(n_layers)]
    layers = tuple(jnp.stack([p[j] for p in per])
                   for j in range(LEAVES_PER_LAYER))
    head = tuple(flat[5 + n_layers * LEAVES_PER_LAYER:])
    return embed, layers, head


def _matmul(precision):
    q_in, q_out = lowprec.rounding(precision)
    return lambda a, b: q_out(jnp.matmul(q_in(a), q_in(b),
                                         precision="highest"))


def _layer_norm(x, gamma, beta, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * gamma + beta


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _drop(x, mask, keep):
    return x * mask.astype(x.dtype) / keep if keep < 1.0 else x


def rows_loss(flat, batch, masks, mlm_denominator, batch_rows, *, config,
              precision):
    """The part of the step's loss that these rows contribute: the sum
    of the parts over row blocks is the whole batch's loss."""
    mm = _matmul(precision)
    n_layers, heads = config["num_hidden_layers"], config[
        "num_attention_heads"]
    eps = config["assumed"]["layer_norm_eps"]
    keep = 1.0 - config["hidden_dropout_prob"]
    (word, types, pos, eg, eb), layers, head = _structure(flat, n_layers)
    inputs, token_types, targets, nsp_labels, weights, valid, positions = batch
    embed_mask, layer_masks = masks
    b, s = inputs.shape
    h = word.shape[1]
    d = h // heads
    x = word[inputs] + types[token_types] + pos[jnp.arange(s)][None]
    x = _drop(_layer_norm(x, eg, eb, eps), embed_mask, keep)
    att_mask = ((jnp.arange(s)[None, :] < valid[:, None])
                .astype(jnp.float32) - 1.0)[:, None, None, :] * 1e9

    def layer(x, leaves_and_masks):
        (w_in, b_in, w_out, b_out, g1, be1, w1, bf1, w2, bf2, g2, be2), \
            (m_att, m_ffn) = leaves_and_masks
        qkv = mm(x, w_in.T) + b_in
        q, k, v = (t.reshape(b, s, heads, d).transpose(0, 2, 1, 3)
                   for t in jnp.split(qkv, 3, axis=-1))
        logits = mm(q, k.transpose(0, 1, 3, 2)) / math.sqrt(d) + att_mask
        att = mm(jax.nn.softmax(logits, axis=-1), v)
        att = mm(att.transpose(0, 2, 1, 3).reshape(b, s, h), w_out.T) + b_out
        x = _layer_norm(x + _drop(att, m_att, keep), g1, be1, eps)
        ffn = mm(_gelu(mm(x, w1.T) + bf1), w2.T) + bf2
        return _layer_norm(x + _drop(ffn, m_ffn, keep), g2, be2, eps), None

    x, _ = jax.lax.scan(layer, x, (layers, layer_masks))
    (wp, bp, wt, bt, gt, bet, wd, bd, wn, bn) = head
    pooled = jnp.tanh(mm(x[:, 0], wp.T) + bp)
    nsp_log = jax.nn.log_softmax(mm(pooled, wn.T) + bn)
    nsp_sum = -jnp.sum(jnp.take_along_axis(
        nsp_log, nsp_labels[:, None], axis=1))
    picked = jnp.take_along_axis(x, positions[:, :, None], axis=1)
    t = _layer_norm(_gelu(mm(picked, wt.T) + bt), gt, bet, eps)
    mlm_log = jax.nn.log_softmax(mm(t, wd.T) + bd)
    ll = jnp.take_along_axis(mlm_log, targets[:, :, None], axis=2)[..., 0]
    return (-jnp.sum(ll * weights) / mlm_denominator
            + nsp_sum / batch_rows)


def _compiled(config, precision, shape):
    """The jitted pieces for one precision and batch shape."""
    n_layers, h = config["num_hidden_layers"], config["hidden_size"]
    b, s = shape
    keep = 1.0 - config["hidden_dropout_prob"]

    @jax.jit
    def make_masks(step_key):
        ks = [jax.random.fold_in(step_key, i) for i in range(1 + 2 * n_layers)]
        draw = lambda k: jax.random.bernoulli(k, keep, (b, s, h))  # noqa: E731
        rest = jnp.stack([draw(k) for k in ks[1:]])
        return draw(ks[0]), rest.reshape(n_layers, 2, b, s, h)

    grads = jax.jit(jax.value_and_grad(
        functools.partial(rows_loss, config=config, precision=precision)))
    return make_masks, grads


def adamw(p, g, m, v, t, opt):
    """The program's AdamW: decay added to the update, on every leaf."""
    b1, b2, eps = opt["beta1"], opt["beta2"], opt["epsilon"]
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * jnp.square(g)
    upd = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps) \
        + opt["wd"] * p
    return p - opt["learning_rate"] * upd, m, v


def follow(config, params0, batches, seed, *, precision="float32",
           row_block=32, rows=None):
    """Train `len(batches)` steps from `params0` on `batches` and return
    what the comparison reads: each step's loss, the per-leaf norm of
    the first gradient, the per-leaf norm of the parameters' change
    after the last step.  `rows` keeps only the first `rows` rows of
    every batch (the half-batch fault)."""
    opt = config["assumed"]["optimizer"]

    @jax.jit
    def update(params, grads, ms, vs, t):
        new = [adamw(p, g, m, v, t, opt)
               for p, g, m, v in zip(params, grads, ms, vs)]
        return tuple(list(x) for x in zip(*new))

    norms = jax.jit(functools.partial(_part_norms,
                                      parts=leaf_parts(config)))
    add = jax.jit(lambda a, b: [x + y for x, y in zip(a, b)])
    params = list(params0)
    ms = [jnp.zeros_like(p) for p in params]
    vs = [jnp.zeros_like(p) for p in params]
    base = jax.random.PRNGKey(seed)
    losses, grad_norms = [], None
    make_masks, grads = _compiled(config, precision, batches[0][0].shape)
    for t, batch in enumerate(batches):
        embed_mask, layer_masks = make_masks(jax.random.fold_in(base, t))
        n = rows or batch[0].shape[0]
        block = min(row_block, n)
        denominator = float(np.sum(batch[4][:n])) + 1.0
        total, loss = None, 0.0
        for r0 in range(0, n, block):
            sl = slice(r0, min(r0 + block, n))
            part, g = grads(params,
                            tuple(jnp.asarray(a[sl]) for a in batch),
                            (embed_mask[sl], layer_masks[:, :, sl]),
                            denominator, float(n))
            loss += float(part)
            total = g if total is None else add(total, g)
        del embed_mask, layer_masks
        losses.append(loss)
        if t == 0:
            grad_norms = np.asarray(norms(total))
        params, ms, vs = update(params, total, ms, vs, float(t + 1))
    change = np.asarray(norms([a - c for a, c in zip(params, params0)]))
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change}
