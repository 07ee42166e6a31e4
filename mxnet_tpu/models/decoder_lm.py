"""Decoder-only language model whose layers differ in kind: full or
sliding-window grouped-query attention with an output gate (a head or
an element) and partial / YaRN rotary embeddings, or a Gated DeltaNet
(linear attention with a gated delta rule); a dense SwiGLU or a
sparse-expert feed-forward with a shared expert (gated or not); RMS
pre-norms, an untied head and a next-token loss (the shapes of
poolside's Laguna family and of Qwen3-Next; nothing of either is
hard-wired but the defaults).

Everything a layer is comes from the config's own per-layer lists, in
HF `config.json` names: `layer_types` (`full_attention` /
`sliding_attention` / `linear_attention`),
`num_attention_heads_per_layer`, `mlp_layer_types` (`dense` /
`sparse`), `rope_parameters` by layer type, `sliding_window`, the
`linear_*` sizes of the DeltaNet; and from four switches that default
to the plain decoder: `attention_output_gate` (`per_head` /
`elementwise`), `qk_norm`, `norm_zero_centered`, `shared_expert_gate`
(docs/decoder_lm.md).  The expert layers are ONE chip's share of an
expert-parallel deployment (ops/moe.py): `num_experts` experts are held
here, ids `first_expert ...`, under a router of `router_width` outputs.

`forward(ids, labels)` returns the scalar loss, so the model trains as
BERT's pre-training block does: `DataParallelTrainer(net, lambda out,
_: out, "adamw", ..., compute_dtype="bfloat16", remat=True)`; every
decoder layer and the head are direct children, which is what the
trainer's `remat` recomputes one at a time.  RECOMPUTED in the backward
pass: a layer's norms, projections, rotary embedding or short
convolution, gates and its feed-forward or expert layer.  KEPT: the
layer's input; the flash attention kernel's output and row statistic
(`flash_attention.RESIDUAL_NAMES`), b*s*heads*head_dim x itemsize +
4*b*heads*s bytes a layer (204 MB at 2 x 8,192 tokens, 48 heads of 128
in bf16), so the forward kernel runs once a layer, not twice; the
gated delta rule's output (`linear_attention.RESIDUAL_NAMES`, 134 MB
at 2 x 8,192 tokens, 32 heads of 128), so the rule's forward runs once
outside its own backward pass.  The rows each held expert got in the
newest step are in `routing_log`, a non-trainable parameter
(`routing_rows()` reads it; the profiler section `moeRouting` reads the
live trainers' copies).

TPU notes: attention is the registry's scaled_dot_product_attention
(the grouped Pallas flash kernels), the delta rule the chunked XLA form
of ops/linear_attention.py, the experts one grouped product
(`moe_ffn`); `jax.named_scope`s name each part in the device trace.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from .. import profiler
from ..gluon.block import HybridBlock
from ..ops import moe as moe_op
from ..ops.nn import rotary_frequencies

_opened_ns = 0      # the clock when the `moeRouting` section's window opened


class DecoderLayer(HybridBlock):
    """Pre-norm decoder layer: x + Mixer(norm(x)), then + FFN(norm(.)).
    The mixer is softmax attention (full or windowed) or, for
    `linear_attention`, a Gated DeltaNet."""

    def __init__(self, config, index, **kwargs):
        super().__init__(**kwargs)
        h, d = config["hidden_size"], config["head_dim"]
        self._kind = config["layer_types"][index]
        self._eps = config.get("rms_norm_eps", 1e-6)
        # norm(x) = x_hat * (1 + w), w from 0, where the model says so
        self._zero_centered = bool(config.get("norm_zero_centered", False))
        gain = "zeros" if self._zero_centered else "ones"
        self._sparse = config["mlp_layer_types"][index] == "sparse"
        get = self.params.get
        self.attn_norm = get("attn_norm", shape=(h,), init=gain)
        if self._kind == "linear_attention":
            hk, hv = (config["linear_num_key_heads"],
                      config["linear_num_value_heads"])
            dk, dv = (config["linear_key_head_dim"],
                      config["linear_value_head_dim"])
            self._linear_dims = hk, hv, dk, dv
            # [q | k | v | z] and [b | a], each part contiguous
            self.qkvz_weight = get(
                "qkvz_weight", shape=(2 * hk * dk + 2 * hv * dv, h))
            self.ba_weight = get("ba_weight", shape=(2 * hv, h))
            self.conv_weight = get(
                "conv_weight", shape=(2 * hk * dk + hv * dv,
                                      config["linear_conv_kernel_dim"]))
            self.a_log = get("a_log", shape=(hv,), init="zeros")
            self.dt_bias = get("dt_bias", shape=(hv,), init="ones")
            self.o_norm = get("o_norm", shape=(dv,), init="ones")
            self.out_weight = get("out_weight", shape=(h, hv * dv))
        else:
            per_layer = config.get("num_attention_heads_per_layer")
            self._heads = per_layer[index] if per_layer \
                else config["num_attention_heads"]
            self._kv_heads = config["num_key_value_heads"]
            self._head_dim = d
            self._window = config["sliding_window"] \
                if self._kind == "sliding_attention" else None
            rope = dict(config["rope_parameters"][self._kind])
            rotary_dim = int(d * rope.pop("partial_rotary_factor", 1.0))
            self._inv_freq, self._attention_factor = rotary_frequencies(
                rotary_dim, **rope)
            # the output gate: one sigmoid a head from a projection of
            # its own (`per_head`), or one an element from the doubled
            # q projection, [q | gate] (`elementwise`)
            self._gate = config.get("attention_output_gate", "per_head")
            if self._gate not in ("per_head", "elementwise"):
                raise ValueError(
                    f"attention_output_gate {self._gate!r}: per_head or "
                    "elementwise")
            self._qk_norm = bool(config.get("qk_norm", False))
            n, kv = self._heads, self._kv_heads
            doubled = 2 if self._gate == "elementwise" else 1
            self.q_weight = get("q_weight", shape=(doubled * n * d, h))
            self.k_weight = get("k_weight", shape=(kv * d, h))
            self.v_weight = get("v_weight", shape=(kv * d, h))
            if self._gate == "per_head":
                self.gate_weight = get("gate_weight", shape=(n, h))
            if self._qk_norm:
                self.q_norm = get("q_norm", shape=(d,), init=gain)
                self.k_norm = get("k_norm", shape=(d,), init=gain)
            self.out_weight = get("out_weight", shape=(h, n * d))
        self.ffn_norm = get("ffn_norm", shape=(h,), init=gain)
        if self._sparse:
            width = config["moe_intermediate_size"]
            shared = config["shared_expert_intermediate_size"]
            held = config["num_experts"]
            self._top_k = config["num_experts_per_tok"]
            self._scale = config.get("moe_routed_scaling_factor", 1.0)
            self._first_expert = config.get("first_expert", 0)
            self._shared_gate = bool(config.get("shared_expert_gate", False))
            self.router_weight = get(
                "router_weight", shape=(h, config.get("router_width", held)))
            self.expert_in_weight = get(
                "expert_in_weight", shape=(held, h, 2 * width))
            self.expert_out_weight = get(
                "expert_out_weight", shape=(held, width, h))
            self.shared_in_weight = get(
                "shared_in_weight", shape=(2 * shared, h))
            self.shared_out_weight = get(
                "shared_out_weight", shape=(h, shared))
            if self._shared_gate:
                self.shared_gate_weight = get(
                    "shared_gate_weight", shape=(1, h))
        else:
            width = config["intermediate_size"]
            self.ffn_in_weight = get("ffn_in_weight", shape=(2 * width, h))
            self.ffn_out_weight = get("ffn_out_weight", shape=(h, width))

    @staticmethod
    def _linear(F, x, weight):
        return F.FullyConnected(x, weight, no_bias=True, flatten=False,
                                num_hidden=weight.shape[0])

    def _norm(self, F, x, gamma):
        return F.rms_norm(x, gamma, eps=self._eps,
                          zero_centered=self._zero_centered)

    def _swiglu_ffn(self, F, u, w_in, w_out):
        return self._linear(F, F.swiglu(self._linear(F, u, w_in)), w_out)

    def _attention(self, F, u, p):
        b, s, _ = u.shape
        n, kv, d = self._heads, self._kv_heads, self._head_dim

        def heads(x, count, norm=None):
            x = x.reshape(b, s, count, d)
            if norm is not None:
                x = self._norm(F, x, norm)
            return F.rotary_embedding(
                x.transpose((0, 2, 1, 3)), inv_freq=self._inv_freq,
                attention_factor=self._attention_factor)

        q = self._linear(F, u, p["q_weight"])
        if self._gate == "elementwise":
            q, gate = q[:, :, :n * d], q[:, :, n * d:]
        q = heads(q, n, p.get("q_norm"))
        k = heads(self._linear(F, u, p["k_weight"]), kv, p.get("k_norm"))
        v = self._linear(F, u, p["v_weight"]).reshape(b, s, kv, d) \
            .transpose((0, 2, 1, 3))
        att = F.scaled_dot_product_attention(
            q, k, v, causal=True, window=self._window)
        if self._gate == "per_head":
            gate = self._linear(F, u, p["gate_weight"])
        gate = F.sigmoid(gate.astype("float32"))
        att = att.transpose((0, 2, 1, 3)) \
            * gate.astype(att.dtype).reshape(b, s, n, -1)
        return self._linear(F, att.reshape(b, s, n * d), p["out_weight"])

    def _linear_attention(self, F, u, p):
        """Gated DeltaNet: q, k, v through a short causal convolution
        and SiLU, q and k l2-normed a head, the gated delta rule with a
        decay and a write strength a value head from `ba`, a gated RMS
        norm a head, the output projection."""
        b, s, _ = u.shape
        hk, hv, dk, dv = self._linear_dims
        key, value = hk * dk, hv * dv
        mixed = self._linear(F, u, p["qkvz_weight"])
        z = mixed[:, :, 2 * key + value:]
        qkv = F.causal_conv1d(mixed[:, :, :2 * key + value],
                              p["conv_weight"])

        def heads(x, count, size):
            return x.reshape(b, s, count, size).transpose((0, 2, 1, 3))

        q = F.l2_norm(heads(qkv[:, :, :key], hk, dk)) * dk ** -0.5
        k = F.l2_norm(heads(qkv[:, :, key:2 * key], hk, dk))
        v = heads(qkv[:, :, 2 * key:], hv, dv)
        ba = self._linear(F, u, p["ba_weight"]).astype("float32") \
            .transpose((0, 2, 1))
        beta = F.sigmoid(ba[:, :hv])
        g = -F.exp(p["a_log"].astype("float32")).reshape(1, hv, 1) \
            * F.softrelu(ba[:, hv:]
                         + p["dt_bias"].astype("float32").reshape(1, hv, 1))
        o = F.gated_delta_rule(q, k, v, g, beta)
        o = F.gated_rms_norm(o.transpose((0, 2, 1, 3)),
                             z.reshape(b, s, hv, dv), p["o_norm"],
                             eps=self._eps)
        return self._linear(F, o.reshape(b, s, value), p["out_weight"])

    def hybrid_forward(self, F, x, **p):
        if self._kind == "linear_attention":
            scope, mixer = "linear_attention", self._linear_attention
        else:
            scope = "attention_window" if self._window else "attention_full"
            mixer = self._attention
        with jax.named_scope(scope):
            a = x + mixer(F, self._norm(F, x, p["attn_norm"]), p)
        u = self._norm(F, a, p["ffn_norm"])
        if not self._sparse:
            with jax.named_scope("dense_ffn"):
                return a + self._swiglu_ffn(F, u, p["ffn_in_weight"],
                                            p["ffn_out_weight"])
        routed, rows = F.moe_ffn(
            u, p["router_weight"], p["expert_in_weight"],
            p["expert_out_weight"], first_expert=self._first_expert,
            top_k=self._top_k, scale=self._scale)
        with jax.named_scope("shared_expert"):
            shared = self._swiglu_ffn(F, u, p["shared_in_weight"],
                                      p["shared_out_weight"])
            if self._shared_gate:
                shared = shared * F.sigmoid(self._linear(
                    F, u, p["shared_gate_weight"]).astype("float32")) \
                    .astype(shared.dtype)
        return a + shared + routed, rows


class LMHead(HybridBlock):
    """Final norm, untied head, mean next-token cross-entropy in
    float32 over the vocabulary rows held here."""

    def __init__(self, config, **kwargs):
        super().__init__(**kwargs)
        h = config["hidden_size"]
        self._eps = config.get("rms_norm_eps", 1e-6)
        self._zero_centered = bool(config.get("norm_zero_centered", False))
        self.norm = self.params.get(
            "norm", shape=(h,),
            init="zeros" if self._zero_centered else "ones")
        self.weight = self.params.get(
            "weight", shape=(config["vocab_size"], h))

    def hybrid_forward(self, F, x, labels, norm, weight):
        with jax.named_scope("lm_head"):
            x = F.rms_norm(x, norm, eps=self._eps,
                           zero_centered=self._zero_centered)
            # float32 logits from the compute dtype's operands (the
            # products are exact in float32, and accumulate there)
            logits = F.FullyConnected(
                x.astype("float32"), weight.astype("float32"), no_bias=True,
                flatten=False, num_hidden=weight.shape[0])
            picked = F.pick(F.log_softmax(logits), labels, axis=-1)
            return -F.mean(picked)


class DecoderLM(HybridBlock):
    def __init__(self, config, **kwargs):
        super().__init__(**kwargs)
        self.config = config
        n_layers = config["num_hidden_layers"]
        self._sparse_layers = [
            i for i in range(n_layers)
            if config["mlp_layer_types"][i] == "sparse"]
        if self._sparse_layers:
            self.routing_log = self.params.get(
                "routing_log", grad_req="null", init="zeros",
                shape=(len(self._sparse_layers), config["num_experts"] + 1))
        self.embed = self.params.get(
            "embed", shape=(config["vocab_size"], config["hidden_size"]))
        self.layers = []
        for i in range(n_layers):
            layer = DecoderLayer(config, i)
            setattr(self, f"layer{i}", layer)
            self.layers.append(layer)
        self.head = LMHead(config)

    def hybrid_forward(self, F, ids, labels, embed, routing_log=None):
        x = F.Embedding(ids, embed, input_dim=embed.shape[0],
                        output_dim=embed.shape[1])
        for i, layer in enumerate(self.layers):
            if i in self._sparse_layers:
                x, rows = layer(x)
                F.moe_routing_log(rows, routing_log,
                                  layer=self._sparse_layers.index(i))
            else:
                x = layer(x)
        return self.head(x, labels)

    def routing_rows(self, log=None):
        """{decoder layer index: float array (held + 1,)}: the rows each
        held expert got in the newest step, then the assignments that
        went to absent experts.  `log` is the routing log's values where
        the block's own are stale: under a `DataParallelTrainer`,
        `trainer.aux_params()[net.routing_log.name]`."""
        if not self._sparse_layers:
            return {}
        if log is None:
            log = self.routing_log.data().asnumpy()
        return {layer: log[row]
                for row, layer in enumerate(self._sparse_layers)}


def routing_stats(rows):
    """One expert layer's line of the `moeRouting` section from its
    (held + 1,) routing counts; `capacity` is the rows the expert path
    worked on in that step (`ops/moe.py:capacity`, the op's own choice
    among its static row counts) and `capacity_share` how full it
    was."""
    held = np.asarray(rows[:-1], np.float64)
    here, total = float(held.sum()), float(np.sum(rows))
    mean = here / len(held) if len(held) else 0.0
    capacity = moe_op.capacity(int(here), int(total))
    return {"rows_per_expert": [int(r) for r in held],
            "rows_here": int(here),
            "share_here": here / total if total else 0.0,
            "max_over_mean": float(held.max() / mean) if mean else 0.0,
            "capacity": capacity,
            "capacity_share": here / capacity if capacity else 0.0}


def moe_routing_stats(newest=False, window=False):
    """The `moeRouting` profiler section: for every live
    `DataParallelTrainer` whose block is a `DecoderLM` with expert
    layers (the newest such trainer alone under `newest=True`; under
    `window=True` those alone that stepped since the section's window
    opened), each layer's routing in the trainer's newest step, read
    from its routing log on demand (reading costs a training window
    nothing: the log leaves the compiled step as an aux output).  Keyed
    `trainer<n>.layer<l>`, n the trainer's serial in this process (and
    `.expert<e>` for the rows of one held expert), under each quantity:
    the shape `/metrics` renders as labelled samples.  A model run
    eagerly has its own `routing_rows()`."""
    from ..parallel import data_parallel

    out = {"layers": 0, "rows_here": {}, "share_here": {},
           "max_over_mean": {}, "capacity": {}, "capacity_share": {},
           "rows_per_expert": {}}
    trainers = [t for t in data_parallel.live_trainers()
                if isinstance(t.block, DecoderLM) and t.block._sparse_layers]
    if window:
        stepped = {record[0] for record in data_parallel.step_log()
                   if record[2] >= _opened_ns}
        trainers = [t for t in trainers if t._serial in stepped]
    for trainer in trainers[-1:] if newest else trainers:
        log = trainer.aux_params()[trainer.block.routing_log.name]
        for layer, rows in trainer.block.routing_rows(log).items():
            key = f"trainer{trainer._serial}.layer{layer}"
            stats = routing_stats(rows)
            out["layers"] += 1
            for name in ("rows_here", "share_here", "max_over_mean",
                         "capacity", "capacity_share"):
                out[name][key] = stats[name]
            for e, count in enumerate(stats["rows_per_expert"]):
                out["rows_per_expert"][f"{key}.expert{e}"] = count
    return out


def reset_moe_routing_stats():
    """Open the section's window, as a reset dump does for every
    section: a trainer that takes no step after this is left out of
    `moe_routing_stats(window=True)` (its log is an older window's)."""
    global _opened_ns
    _opened_ns = time.perf_counter_ns()


def _routing_table(stats):
    out = ["MoE Routing (newest step, by expert layer):"]
    for key in sorted(stats["rows_here"]):
        out.append(f"  {key}: rows here {stats['rows_here'][key]}, share "
                   f"{stats['share_here'][key]:.4f}, max/mean "
                   f"{stats['max_over_mean'][key]:.3f}, capacity "
                   f"{stats['capacity'][key]} filled "
                   f"{stats['capacity_share'][key]:.3f}")
    return out


profiler.register_section(
    "moeRouting", lambda: moe_routing_stats(window=True),
    reset_moe_routing_stats, _routing_table)
