"""BERT (ref workload: BASELINE config 'BERT-base MLM pretrain
(GluonNLP, Trainer + kvstore all-reduce on pod)'; model structure after
the GluonNLP-era BERTModel: embeddings + transformer encoder + MLM/NSP
heads).

TPU notes: attention uses the fused scaled_dot_product_attention op
(pallas flash path on TPU); everything hybridizes into one XLA step.
"""
from __future__ import annotations

import math

from ..gluon import nn
from ..gluon.block import HybridBlock


class BERTEncoderLayer(HybridBlock):
    def __init__(self, units=768, hidden_size=3072, num_heads=12,
                 dropout=0.1, **kwargs):
        super().__init__(**kwargs)
        self._units = units
        self._num_heads = num_heads
        self.attn_in_weight = self.params.get(
            "attn_in_weight", shape=(3 * units, units))
        self.attn_in_bias = self.params.get(
            "attn_in_bias", shape=(3 * units,), init="zeros")
        self.attn_out_weight = self.params.get(
            "attn_out_weight", shape=(units, units))
        self.attn_out_bias = self.params.get(
            "attn_out_bias", shape=(units,), init="zeros")
        self.attn_ln = nn.LayerNorm(in_channels=units)
        self.ffn1 = nn.Dense(hidden_size, flatten=False)
        self.ffn2 = nn.Dense(units, flatten=False)
        self.ffn_ln = nn.LayerNorm(in_channels=units)
        self.dropout = nn.Dropout(dropout)

    def hybrid_forward(self, F, x, mask=None, attn_in_weight=None,
                       attn_in_bias=None, attn_out_weight=None,
                       attn_out_bias=None):
        att = F.multihead_attention(x, x, x, attn_in_weight, attn_in_bias,
                                    attn_out_weight, attn_out_bias, mask,
                                    num_heads=self._num_heads)
        x = self.attn_ln(x + self.dropout(att))
        h = self.ffn2(F.LeakyReLU(self.ffn1(x), act_type="gelu"))
        return self.ffn_ln(x + self.dropout(h))


class BERTEncoder(HybridBlock):
    def __init__(self, num_layers=12, units=768, hidden_size=3072,
                 num_heads=12, dropout=0.1, **kwargs):
        super().__init__(**kwargs)
        self.layers = nn.HybridSequential()
        for _ in range(num_layers):
            self.layers.add(BERTEncoderLayer(units, hidden_size, num_heads,
                                             dropout))

    def hybrid_forward(self, F, x, mask=None):
        for layer in self.layers:
            x = layer(x, mask)
        return x


class BERTModel(HybridBlock):
    """BERT backbone + MLM decoder + NSP classifier."""

    def __init__(self, vocab_size=30522, units=768, hidden_size=3072,
                 num_layers=12, num_heads=12, max_length=512,
                 type_vocab_size=2, dropout=0.1, use_pooler=True,
                 use_decoder=True, use_classifier=True, **kwargs):
        # use_pooler/use_decoder/use_classifier follow gluonnlp's
        # BERTModel: fine-tuning builds the backbone WITHOUT the MLM
        # decoder / NSP classifier heads (their params would otherwise
        # sit deferred-uninitialized in the block tree)
        super().__init__(**kwargs)
        if use_classifier and not use_pooler:
            raise ValueError(
                "use_classifier=True requires use_pooler=True (the NSP "
                "head reads the pooled [CLS]); gluonnlp enforces the "
                "same combination")
        self._units = units
        self._use_pooler = use_pooler
        self._use_decoder = use_decoder
        self._use_classifier = use_classifier
        self.word_embed = nn.Embedding(vocab_size, units)
        self.token_type_embed = nn.Embedding(type_vocab_size, units)
        self.position_embed = nn.Embedding(max_length, units)
        self.embed_ln = nn.LayerNorm(in_channels=units)
        self.embed_dropout = nn.Dropout(dropout)
        self.encoder = BERTEncoder(num_layers, units, hidden_size,
                                   num_heads, dropout)
        if use_pooler:
            self.pooler = nn.Dense(units, flatten=False,
                                   activation="tanh")
        if use_decoder:
            # MLM head (decoder shares transform; tied embedding
            # optional)
            self.mlm_transform = nn.Dense(units, flatten=False)
            self.mlm_ln = nn.LayerNorm(in_channels=units)
            self.mlm_decoder = nn.Dense(vocab_size, flatten=False)
        if use_classifier:
            self.nsp_classifier = nn.Dense(2, flatten=False)

    def _encode_sequence(self, inputs, token_types, valid_length=None):
        """Embeddings + attention-masked encoder stack — shared by the
        pretraining heads and fine-tune classifiers (ref: gluonnlp
        BERTModel's encode path reused by BERTClassifier)."""
        from .. import ndarray as F

        seq_len = inputs.shape[1]
        # arange_like, not arange: a creation op lands on the DEFAULT
        # context (mx.cpu() — the host, even beside a chip), while this
        # one is computed from `inputs` and so lives where they live
        positions = F.contrib.arange_like(inputs, axis=1)
        x = self.word_embed(inputs) + self.token_type_embed(token_types)
        x = x + self.position_embed(positions)
        x = self.embed_dropout(self.embed_ln(x))
        mask = None
        if valid_length is not None:
            steps = positions.astype("float32")
            m = F.broadcast_lesser(
                steps.reshape(1, -1), valid_length.reshape(-1, 1))
            mask = (m.reshape(m.shape[0], 1, 1, seq_len) - 1.0) * 1e9
        return self.encoder(x, mask)

    def pool(self, seq):
        """[CLS] representation through the tanh pooler."""
        return self.pooler(seq.slice_axis(1, 0, 1).reshape(
            seq.shape[0], self._units))

    def hybrid_forward(self, F, inputs, token_types, valid_length=None,
                       masked_positions=None):
        """Full heads: (mlm_scores, nsp_scores) — the pretraining
        contract.  With use_decoder=False/use_classifier=False
        (fine-tuning backbones) returns (sequence, pooled) or just the
        sequence, matching gluonnlp's output arity rules.

        `masked_positions` (b, K) int32 — gluonnlp's BERTModel
        contract: the MLM head decodes ONLY the gathered positions,
        giving (b, K, vocab).  At seq 128 the all-positions vocab
        projection is ~35% of the training step's FLOPs for ~15%
        masked tokens — the gather is both the reference recipe and
        the throughput win.  Omitted: decode every position (b, S,
        vocab), the fine-tune/scoring form."""
        seq = self._encode_sequence(inputs, token_types, valid_length)
        if not (self._use_decoder or self._use_classifier):
            if not self._use_pooler:
                return seq
            return seq, self.pool(seq)
        mlm_in = seq
        if self._use_decoder and masked_positions is not None:
            b, S = inputs.shape[0], inputs.shape[1]
            K = masked_positions.shape[1]
            flat = seq.reshape(b * S, self._units)
            offsets = F.contrib.arange_like(inputs, axis=0) \
                .astype("int32").reshape(b, 1) * S
            fidx = (masked_positions.astype("int32") + offsets) \
                .reshape(b * K)
            mlm_in = F.take(flat, fidx).reshape(b, K, self._units)
        mlm = self.mlm_decoder(
            self.mlm_ln(F.LeakyReLU(self.mlm_transform(mlm_in),
                                    act_type="gelu"))) \
            if self._use_decoder else None
        # pool only when the NSP head consumes it (an MLM-only model
        # must not pay for a discarded pooler forward)
        nsp = self.nsp_classifier(self.pool(seq)) \
            if self._use_classifier else None
        if mlm is not None and nsp is not None:
            return mlm, nsp
        return mlm if mlm is not None else nsp


def bert_base(vocab_size=30522, **kwargs):
    """BERT-base: 12 layers, 768 units, 12 heads (the BASELINE config)."""
    return BERTModel(vocab_size, 768, 3072, 12, 12, **kwargs)


def bert_large(vocab_size=30522, **kwargs):
    return BERTModel(vocab_size, 1024, 4096, 24, 16, **kwargs)


def bert_tiny(vocab_size=1000, **kwargs):
    """Small config for tests."""
    return BERTModel(vocab_size, 64, 128, 2, 4, max_length=128, **kwargs)
