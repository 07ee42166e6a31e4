"""Decoder-only language model whose layers differ in kind: full or
sliding-window grouped-query attention with an output gate (a head, an
element or none) and partial / YaRN rotary embeddings (or none), a
Gated DeltaNet (linear attention with a gated delta rule), or a Mamba-2
mixer (the SSD state-space scan); a dense SwiGLU or a sparse-expert
feed-forward with a shared expert (gated or not; SwiGLU or relu^2
experts under a softmax or a sigmoid router); a layer of both parts
or of one; RMS pre-norms, an untied head and a next-token loss (the
shapes of poolside's Laguna family, of Qwen3-Next and of Nemotron-H;
nothing of any is hard-wired but the defaults).

Everything a layer is comes from the config's own per-layer lists, in
HF `config.json` names: `layer_types` (`full_attention` /
`sliding_attention` / `linear_attention` / `mamba`, or None: no
mixer), `num_attention_heads_per_layer`, `mlp_layer_types` (`dense` /
`sparse`, or None: no feed-forward), `rope_parameters` by layer type
(none for a kind: no rotation), `sliding_window`, the `linear_*` sizes
of the DeltaNet, the `mamba_*`, `n_groups`, `ssm_state_size`,
`chunk_size`, `conv_kernel`, `use_conv_bias` of the Mamba-2 mixer; and
from six switches that default to the plain decoder:
`attention_output_gate` (`per_head` / `elementwise` / None),
`qk_norm`, `norm_zero_centered`, `shared_expert_gate`,
`router_scoring` (`softmax` / `sigmoid`, the latter with a
non-trainable correction bias a layer, held by the model) and
`expert_activation` (`swiglu` / `relu2`) (docs/decoder_lm.md).  The
expert layers are ONE chip's share of an expert-parallel deployment
(ops/moe.py): `num_experts` experts are held here, ids `first_expert
...`, under a router of `router_width` outputs.

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
outside its own backward pass; the SSD scan's output
(`ssm.RESIDUAL_NAMES`, 67 MB at 8,192 tokens, 64 heads of 64), for the
same reason.  The rows each held expert got in the newest step are in
`routing_log`, a non-trainable parameter (`routing_rows()` reads it;
the profiler section `moeRouting` reads the live trainers' copies).

TPU notes: attention is the registry's scaled_dot_product_attention
(the grouped Pallas flash kernels), the delta rule the kernels of
ops/pallas/delta_rule.py, the SSD the chunked `jax.numpy` form of
ops/ssm.py, the experts one grouped product (`moe_ffn`);
`jax.named_scope`s name each part in the device trace.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from .. import profiler
from ..gluon.block import HybridBlock
from ..ops import linear_attention, moe as moe_op
from ..ops.nn import rotary_frequencies

_opened_ns = 0      # the clock when the `moeRouting` section's window opened


class DecoderLayer(HybridBlock):
    """Pre-norm decoder layer: x + Mixer(norm(x)), then + FFN(norm(.)).
    The mixer is softmax attention (full or windowed), for
    `linear_attention` a Gated DeltaNet, for `mamba` a Mamba-2 mixer.
    A layer of ONE part has a None mixer (`layer_types`) or a None
    feed-forward (`mlp_layer_types`): x + Part(norm(x)) alone."""

    def __init__(self, config, index, **kwargs):
        super().__init__(**kwargs)
        h, d = config["hidden_size"], config["head_dim"]
        self._kind = config["layer_types"][index]
        self._eps = config.get("rms_norm_eps", 1e-6)
        # norm(x) = x_hat * (1 + w), w from 0, where the model says so
        self._zero_centered = bool(config.get("norm_zero_centered", False))
        gain = "zeros" if self._zero_centered else "ones"
        self._ffn = config["mlp_layer_types"][index]
        self._sparse = self._ffn == "sparse"
        get = self.params.get
        if self._kind is not None:
            self.attn_norm = get("attn_norm", shape=(h,), init=gain)
        if self._kind == "mamba":
            heads, size = config["mamba_num_heads"], config["mamba_head_dim"]
            groups, state = config["n_groups"], config["ssm_state_size"]
            self._mamba_dims = heads, size, groups, state
            self._chunk = config["chunk_size"]
            inner, conv = heads * size, heads * size + 2 * groups * state
            # [x | B | C | z], the convolution's channels first, and dt
            self.in_proj_weight = get(
                "in_proj_weight", shape=(conv + inner, h))
            self.dt_weight = get("dt_weight", shape=(heads, h))
            self.conv_weight = get(
                "conv_weight", shape=(conv, config["conv_kernel"]))
            if config.get("use_conv_bias", False):
                self.conv_bias = get("conv_bias", shape=(conv,),
                                     init="zeros")
            self.a_log = get("a_log", shape=(heads,), init="zeros")
            self.d = get("d", shape=(heads,), init="ones")
            self.dt_bias = get("dt_bias", shape=(heads,), init="zeros")
            self.mamba_norm = get("mamba_norm", shape=(inner,), init="ones")
            self.out_weight = get("out_weight", shape=(h, inner))
        elif self._kind == "linear_attention":
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
        elif self._kind is not None:
            per_layer = config.get("num_attention_heads_per_layer")
            self._heads = per_layer[index] if per_layer \
                else config["num_attention_heads"]
            self._kv_heads = config["num_key_value_heads"]
            self._head_dim = d
            self._window = config["sliding_window"] \
                if self._kind == "sliding_attention" else None
            # no rotary embedding where the kind has no rope parameters
            rope = config.get("rope_parameters", {}).get(self._kind)
            self._inv_freq, self._attention_factor = None, 1.0
            if rope is not None:
                rope = dict(rope)
                rotary_dim = int(d * rope.pop("partial_rotary_factor", 1.0))
                self._inv_freq, self._attention_factor = rotary_frequencies(
                    rotary_dim, **rope)
            # the output gate: one sigmoid a head from a projection of
            # its own (`per_head`), one an element from the doubled q
            # projection, [q | gate] (`elementwise`), or none (None)
            self._gate = config.get("attention_output_gate", "per_head")
            if self._gate not in ("per_head", "elementwise", None):
                raise ValueError(
                    f"attention_output_gate {self._gate!r}: per_head or "
                    "elementwise (or None: no gate)")
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
        if self._ffn is not None:
            self.ffn_norm = get("ffn_norm", shape=(h,), init=gain)
        if self._sparse:
            width = config["moe_intermediate_size"]
            shared = config["shared_expert_intermediate_size"]
            held = config["num_experts"]
            self._top_k = config["num_experts_per_tok"]
            self._scale = config.get("moe_routed_scaling_factor", 1.0)
            self._first_expert = config.get("first_expert", 0)
            self._shared_gate = bool(config.get("shared_expert_gate", False))
            # `swiglu` ([gate | up] packed) or `relu2` (no gate)
            self._activation = config.get("expert_activation", "swiglu")
            self._scoring = config.get("router_scoring", "softmax")
            rows = 2 if self._activation == "swiglu" else 1
            self.router_weight = get(
                "router_weight", shape=(h, config.get("router_width", held)))
            self.expert_in_weight = get(
                "expert_in_weight", shape=(held, h, rows * width))
            self.expert_out_weight = get(
                "expert_out_weight", shape=(held, width, h))
            self.shared_in_weight = get(
                "shared_in_weight", shape=(rows * shared, h))
            self.shared_out_weight = get(
                "shared_out_weight", shape=(h, shared))
            if self._shared_gate:
                self.shared_gate_weight = get(
                    "shared_gate_weight", shape=(1, h))
        elif self._ffn is not None:
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

    def _shared_ffn(self, F, u, w_in, w_out):
        if self._activation == "swiglu":
            return self._swiglu_ffn(F, u, w_in, w_out)
        return self._linear(F, F.squared_relu(self._linear(F, u, w_in)),
                            w_out)

    def _attention(self, F, u, p):
        n, kv, d = self._heads, self._kv_heads, self._head_dim
        # what q and k get on their way to the kernel's layout
        how = dict(inv_freq=self._inv_freq,
                   attention_factor=self._attention_factor)
        if self._qk_norm:
            how.update(norm="rms", eps=self._eps,
                       zero_centered=self._zero_centered)
        # [q | gate] where the gate is an element's
        q = self._linear(F, u, p["q_weight"])
        k = self._linear(F, u, p["k_weight"])
        v = self._linear(F, u, p["v_weight"])
        with jax.named_scope("prologue"):
            q_heads = F.split_heads(q, p.get("q_norm"), heads=n, head_dim=d,
                                    **how)
            k = F.split_heads(k, p.get("k_norm"), heads=kv, head_dim=d,
                              **how)
            v = F.split_heads(v, heads=kv, head_dim=d)
        att = F.scaled_dot_product_attention(
            q_heads, k, v, causal=True, window=self._window)
        if self._gate is None:
            b, _, s, _ = att.shape
            return self._linear(F, att.transpose((0, 2, 1, 3)).reshape(
                (b, s, n * d)), p["out_weight"])
        if self._gate == "per_head":
            gate, column = self._linear(F, u, p["gate_weight"]), 0
        else:
            gate, column = q, n * d
        with jax.named_scope("epilogue"):
            att = F.merge_heads(att, gate, gate_kind=self._gate,
                                column=column)
        return self._linear(F, att, p["out_weight"])

    def _linear_attention(self, F, u, p):
        """Gated DeltaNet: q, k, v through a short causal convolution
        and SiLU, q and k l2-normed a head, the gated delta rule with a
        decay and a write strength a value head from `ba`, a gated RMS
        norm a head, the output projection."""
        hk, hv, dk, dv = self._linear_dims
        key, value = hk * dk, hv * dv
        # the rule works on its heads a group at a time: the passes
        # around it write and read them group-major
        groups = linear_attention.head_groups(u.shape[0], hk, hv)
        mixed = self._linear(F, u, p["qkvz_weight"])
        # over [q | k | v], the first columns of `mixed`: the conv
        # weight's channels
        qkv = F.causal_conv1d(mixed, p["conv_weight"])
        with jax.named_scope("prologue"):
            q = F.split_heads(qkv, heads=hk, head_dim=dk, norm="l2",
                              scale=dk ** -0.5, groups=groups)
            k = F.split_heads(qkv, heads=hk, head_dim=dk, column=key,
                              norm="l2", groups=groups)
            v = F.split_heads(qkv, heads=hv, head_dim=dv, column=2 * key,
                              groups=groups)
        # the write strength and the log decay a value head, (b, hv, s)
        ba = self._linear(F, u, p["ba_weight"]).astype("float32")
        beta = F.sigmoid(ba[:, :, :hv]).transpose((0, 2, 1))
        g = (-F.exp(p["a_log"].astype("float32")) * F.softrelu(
            ba[:, :, hv:] + p["dt_bias"].astype("float32"))) \
            .transpose((0, 2, 1))
        o = F.gated_delta_rule(q, k, v, g, beta)
        with jax.named_scope("epilogue"):
            # z, the norm's gate, where `mixed` holds it
            o = F.merge_heads(o, mixed, p["o_norm"], gate_kind="rms_silu",
                              column=2 * key + value, eps=self._eps,
                              groups=groups)
        return self._linear(F, o, p["out_weight"])

    def _mamba(self, F, u, p):
        """Mamba-2: [x | B | C] through a short causal convolution with
        a bias and SiLU, the step size dt = softplus(. + dt_bias) and
        the decay A = -exp(A_log) a head, the SSD scan with its skip D,
        then rms_norm(y * silu(z)) over each group's heads times the
        gain, and the output projection."""
        heads, size, groups, state = self._mamba_dims
        inner, conv = heads * size, heads * size + 2 * groups * state
        b, s, _ = u.shape
        mixed = self._linear(F, u, p["in_proj_weight"])
        xbc = F.causal_conv1d(mixed, p["conv_weight"], p.get("conv_bias"))
        x = xbc[:, :, :inner].reshape((b, s, heads, size))
        bc = xbc[:, :, inner:].reshape((b, s, 2 * groups, state))
        dt = F.softrelu(self._linear(F, u, p["dt_weight"]).astype("float32")
                        + p["dt_bias"].astype("float32"))
        y = F.ssd(x, dt, -F.exp(p["a_log"].astype("float32")),
                  bc[:, :, :groups], bc[:, :, groups:],
                  p["d"].astype("float32"), chunk=self._chunk)
        y = F.gated_rms_norm(y.reshape((b, s, inner)),
                             mixed[:, :, conv:conv + inner], p["mamba_norm"],
                             eps=self._eps, group_size=inner // groups,
                             gate_first=True)
        return self._linear(F, y, p["out_weight"])

    def hybrid_forward(self, F, x, correction_bias=None, **p):
        if self._kind is not None:
            if self._kind == "mamba":
                scope, mixer = "mamba", self._mamba
            elif self._kind == "linear_attention":
                scope, mixer = "linear_attention", self._linear_attention
            else:
                scope = "attention_window" if self._window \
                    else "attention_full"
                mixer = self._attention
            with jax.named_scope(scope):
                x = x + mixer(F, self._norm(F, x, p["attn_norm"]), p)
        if self._ffn is None:
            return x
        u = self._norm(F, x, p["ffn_norm"])
        if not self._sparse:
            with jax.named_scope("dense_ffn"):
                return x + self._swiglu_ffn(F, u, p["ffn_in_weight"],
                                            p["ffn_out_weight"])
        routed, rows = F.moe_ffn(
            u, p["router_weight"], p["expert_in_weight"],
            p["expert_out_weight"], correction_bias,
            first_expert=self._first_expert, top_k=self._top_k,
            scale=self._scale, scoring=self._scoring,
            activation=self._activation)
        with jax.named_scope("shared_expert"):
            shared = self._shared_ffn(F, u, p["shared_in_weight"],
                                      p["shared_out_weight"])
            if self._shared_gate:
                shared = shared * F.sigmoid(self._linear(
                    F, u, p["shared_gate_weight"]).astype("float32")) \
                    .astype(shared.dtype)
        return x + shared + routed, rows


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
        # a sigmoid router's choice bias, a row an expert layer: not
        # trained by the gradient, held HERE so that the layers hold
        # trainable parameters alone (the trainer's `remat` recomputes
        # only such children)
        self._biased = self._sparse_layers and config.get(
            "router_scoring", "softmax") == "sigmoid"
        if self._biased:
            self.correction_bias = self.params.get(
                "correction_bias", grad_req="null", init="zeros",
                shape=(len(self._sparse_layers),
                       config.get("router_width", config["num_experts"])))
        self.embed = self.params.get(
            "embed", shape=(config["vocab_size"], config["hidden_size"]))
        self.layers = []
        for i in range(n_layers):
            layer = DecoderLayer(config, i)
            setattr(self, f"layer{i}", layer)
            self.layers.append(layer)
        self.head = LMHead(config)

    def hybrid_forward(self, F, ids, labels, embed, routing_log=None,
                       correction_bias=None):
        x = F.Embedding(ids, embed, input_dim=embed.shape[0],
                        output_dim=embed.shape[1])
        for i, layer in enumerate(self.layers):
            if i in self._sparse_layers:
                row = self._sparse_layers.index(i)
                x, rows = layer(x, correction_bias[row]) \
                    if self._biased else layer(x)
                F.moe_routing_log(rows, routing_log, layer=row)
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
