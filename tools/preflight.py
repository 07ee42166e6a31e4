"""HBM-fit + step-cost preflight for the BASELINE workloads.

Ref role: the reference community sizes GPU jobs from docs/faq/perf.md
batch tables and trial-and-error; the TPU-native replacement computes
the answer before the first chip-second is spent (SURVEY §7 hard parts
3/4/6): for each BASELINE config at its REAL scale —

  lenet        bs 64           MNIST 28x28
  resnet50     bs 256 @ 224px  NHWC bf16 (BASELINE config #2)
  bert         bs 256 seq 128  MLM+NSP bf16 (north star, config #3)
  transformer  bs 64  seq 64   big WMT14-style bf16 (config #4)
  deepar       bs 64  T 96     LSTM forecaster (config #5)

— lower the FULL donated train step and report:

- on TPU: the compiled executable's memory_analysis() (argument /
  output / temp / code bytes — XLA's exact HBM budget) and post-fusion
  cost_analysis() (flops, bytes accessed) => predicted step time, MFU,
  and the bandwidth-implied MFU ceiling. Exits nonzero on HBM overflow.
- off TPU: the HLO lowering's flop count plus the static tier computed
  analytically (params + grads + optimizer states + batch), asserting
  the static tier leaves >=30% of HBM for activations.

Usage:
  python tools/preflight.py                 # all five configs
  python tools/preflight.py bert resnet50   # a subset
Prints one JSON line per config; `--markdown` emits the
docs/WORKLOADS.md table rows instead.
"""
import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "examples"))

HBM_BYTES = {  # per-chip HBM by generation (public spec sheets)
    "v5 lite": 16e9, "v5litepod": 16e9, "v5e": 16e9,
    "v5p": 95e9,
    "v6": 32e9, "trillium": 32e9,
    "v4": 32e9, "v3": 32e9, "v2": 16e9,
}
DEFAULT_HBM = 16e9  # size for v5e when probing off-chip


def _hbm_capacity(dev):
    if dev.platform != "tpu":
        return DEFAULT_HBM
    kind = dev.device_kind.lower()
    for key, val in HBM_BYTES.items():
        if key in kind:
            return val
    raise ValueError(
        f"no published HBM size for device_kind {dev.device_kind!r}; "
        "add it to HBM_BYTES with its source")


# ---------------------------------------------------------------------------
# workload builders (same construction as tools/bench_workloads.py /
# bench.py — THE trainers the benches time, at BASELINE scale)
# ---------------------------------------------------------------------------

class _Identity:
    def __call__(self, out, _):
        return out


def _build_lenet(bs=64):
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel import data_parallel

    net = nn.HybridSequential()
    net.add(nn.Conv2D(20, 5, activation="relu"), nn.MaxPool2D(2, 2),
            nn.Conv2D(50, 5, activation="relu"), nn.MaxPool2D(2, 2),
            nn.Flatten(), nn.Dense(500, activation="relu"),
            nn.Dense(10))
    net.initialize(mx.init.Xavier())
    trainer = data_parallel.DataParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1, "momentum": 0.9})
    x = np.zeros((bs, 1, 28, 28), np.float32)
    y = np.zeros((bs,), np.float32)
    return trainer, x, y, {"batch_size": bs}


def _build_resnet50(bs=256, image=224):
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.parallel import data_parallel

    net = vision.resnet50_v1(layout="NHWC")
    net.initialize(mx.init.Xavier())
    trainer = data_parallel.DataParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1, "momentum": 0.9},
        compute_dtype="bfloat16")
    x = np.zeros((bs, image, image, 3), np.float32)
    y = np.zeros((bs,), np.float32)
    return trainer, x, y, {"batch_size": bs, "image": image}


def _build_bert(bs=256, seq_len=128):
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.models import bert as bert_mod
    from mxnet_tpu.parallel import data_parallel

    sys.path.insert(0, os.path.join(REPO, "examples", "bert"))
    from pretrain_bert import BERTForPretrain, synthetic_batch

    vocab = 30522
    model = bert_mod.bert_base(vocab_size=vocab)
    net = BERTForPretrain(model, vocab)
    net.initialize(mx.init.Xavier())
    trainer = data_parallel.DataParallelTrainer(
        net, _Identity(), "adamw", {"learning_rate": 1e-4, "wd": 0.01},
        compute_dtype="bfloat16")
    rng = np.random.RandomState(0)
    x = synthetic_batch(rng, bs, seq_len, vocab)
    y = np.zeros((bs,), np.float32)
    return trainer, x, y, {"batch_size": bs, "seq_len": seq_len}


def _build_transformer(bs=64, seq_len=64):
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.models import transformer as tfm
    from mxnet_tpu.parallel import data_parallel

    sys.path.insert(0, os.path.join(REPO, "examples", "nmt"))
    from train_transformer import (LabelSmoothedCE, Seq2SeqTrainNet,
                                   synthetic_pairs)

    vocab = 32000
    net = Seq2SeqTrainNet(tfm.transformer_big(vocab, vocab))
    net.initialize(mx.init.Xavier())
    trainer = data_parallel.DataParallelTrainer(
        net, LabelSmoothedCE(), "adam",
        {"learning_rate": 3e-4, "beta2": 0.98},
        compute_dtype="bfloat16")
    rng = np.random.RandomState(0)
    src, tgt_in, tgt_out = synthetic_pairs(rng, bs, seq_len, vocab)
    return (trainer, (src, tgt_in), tgt_out,
            {"batch_size": bs, "seq_len": seq_len})


def _build_deepar(bs=64, context_length=72, prediction_length=24):
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import models
    from mxnet_tpu.parallel import data_parallel

    sys.path.insert(0, os.path.join(REPO, "examples", "forecasting"))
    from train_deepar import synthetic_series

    net = models.deepar(40, 2)
    net.initialize(mx.init.Xavier())
    trainer = data_parallel.DataParallelTrainer(
        net, _Identity(), "adam", {"learning_rate": 1e-3})
    rng = np.random.RandomState(0)
    T = context_length + prediction_length
    x = synthetic_series(rng, bs, T).astype(np.float32)
    y = np.zeros((bs,), np.float32)
    return trainer, x, y, {"batch_size": bs, "series_length": T}


BUILDERS = {
    "lenet": _build_lenet,
    "resnet50": _build_resnet50,
    "bert": _build_bert,
    "transformer": _build_transformer,
    "deepar": _build_deepar,
}


# ---------------------------------------------------------------------------
# the preflight itself
# ---------------------------------------------------------------------------

def _static_bytes(trainer):
    """Analytic static tier: master params + grads + optimizer states
    (+ the bf16 compute copy when multi-precision is on)."""
    import numpy as np

    param_b = sum(int(np.prod(p.shape)) * p.dtype.itemsize
                  for p in trainer._params)
    n_state_slots = 0
    if trainer._states is not None:
        import jax

        state_b = sum(int(np.prod(s.shape)) * s.dtype.itemsize
                      for s in jax.tree_util.tree_leaves(trainer._states))
    else:
        # states not materialized off-build: assume adam-class 2 slots
        opt = str(trainer._opt_name or "sgd").lower()
        n_state_slots = 2 if "adam" in opt or "lamb" in opt else 1
        state_b = param_b * n_state_slots
    grad_b = param_b
    bf16_copy = param_b // 2 if trainer._compute_dtype else 0
    return param_b, grad_b, state_b, bf16_copy


def preflight(name, scale_kw=None):
    import jax
    import jax.numpy as jnp

    from bench import (_hbm_bw, _peak_flops, _roofline_bound, _step_cost)
    from mxnet_tpu import random as _random

    trainer, x, y, meta = BUILDERS[name](**(scale_kw or {}))
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    hbm = _hbm_capacity(dev)

    trainer.build(x)

    rec = {"config": name, "platform": dev.platform,
           "device_kind": dev.device_kind, **meta}

    xj = tuple(jnp.asarray(v) for v in x) if isinstance(
        x, (tuple, list)) else jnp.asarray(x)
    lowered = trainer._step_fn.lower(
        trainer._params, trainer._states, xj, jnp.asarray(y),
        _random.next_key(), jnp.asarray(trainer._lr, jnp.float32),
        jnp.asarray(3.0, jnp.float32))

    param_b, grad_b, state_b, bf16_b = _static_bytes(trainer)
    static_b = param_b + grad_b + state_b + bf16_b
    rec.update(param_mb=round(param_b / 1e6, 1),
               static_mb=round(static_b / 1e6, 1),
               hbm_gb=round(hbm / 1e9, 1))

    flops = nbytes = None
    if on_tpu:
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        temp_b = int(getattr(mem, "temp_size_in_bytes", 0))
        arg_b = int(getattr(mem, "argument_size_in_bytes", 0))
        out_b = int(getattr(mem, "output_size_in_bytes", 0))
        code_b = int(getattr(mem, "generated_code_size_in_bytes", 0))
        # args and outputs alias (donated params/states), so peak live
        # HBM ~= arguments + temps + code
        total_b = arg_b + temp_b + code_b
        rec.update(argument_mb=round(arg_b / 1e6, 1),
                   temp_mb=round(temp_b / 1e6, 1),
                   output_mb=round(out_b / 1e6, 1),
                   code_mb=round(code_b / 1e6, 1),
                   peak_hbm_gb=round(total_b / 1e9, 3),
                   fits=bool(total_b < hbm))
        cost = compiled.cost_analysis()
        c = cost[0] if isinstance(cost, (list, tuple)) else cost
        flops = float(c.get("flops", 0.0)) or None
        nbytes = float(c.get("bytes accessed", 0.0)) or None
    else:
        # off-chip: flops from the HLO lowering; fit from the static
        # tier with >=30% headroom left for activations
        try:
            cost = lowered.cost_analysis()
            c = cost[0] if isinstance(cost, (list, tuple)) else cost
            flops = float(c.get("flops", 0.0)) or None
            nbytes = float(c.get("bytes accessed", 0.0)) or None
        except Exception:
            pass
        rec.update(fits=bool(static_b < 0.7 * hbm))

    if flops:
        rec["gflops_per_step"] = round(flops / 1e9, 1)
        peak = _peak_flops(dev.device_kind) if on_tpu else None
        bound = _roofline_bound(flops, nbytes, dev)
        if bound is not None:
            rec["roofline_mfu_bound"] = bound
        if peak:
            bw = _hbm_bw(dev.device_kind)
            # predicted step time: max of compute time and HBM time
            t_pred = max(flops / peak, (nbytes / bw) if (nbytes and bw)
                         else 0.0)
            rec["predicted_step_ms"] = round(t_pred * 1e3, 2)
            rec["predicted_mfu"] = round(flops / peak / t_pred, 4)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("configs", nargs="*", default=list(BUILDERS),
                    help=f"subset of {list(BUILDERS)}")
    ap.add_argument("--markdown", action="store_true",
                    help="emit docs/WORKLOADS.md table rows")
    args = ap.parse_args()

    # the platform is what the caller asked for: the chip by default,
    # the static tier only under an explicit JAX_PLATFORMS=cpu; a
    # backend that does not come up is an error, not a reason to switch
    rows, bad = [], []
    for name in (args.configs or list(BUILDERS)):
        rec = preflight(name)
        rows.append(rec)
        if not rec.get("fits", True):
            bad.append(name)
        if not args.markdown:
            print(json.dumps(rec))
    if args.markdown:
        print("| config | batch | params (MB) | peak HBM (GB) | "
              "GFLOP/step | pred. step (ms) | pred. MFU | "
              "roofline bound | fits 16G |")
        print("|---|---|---|---|---|---|---|---|---|")
        for r in rows:
            print(f"| {r['config']} | {r.get('batch_size')} "
                  f"| {r.get('param_mb')} "
                  f"| {r.get('peak_hbm_gb', '—')} "
                  f"| {r.get('gflops_per_step', '—')} "
                  f"| {r.get('predicted_step_ms', '—')} "
                  f"| {r.get('predicted_mfu', '—')} "
                  f"| {r.get('roofline_mfu_bound', '—')} "
                  f"| {'yes' if r.get('fits') else 'NO'} |")
    if bad:
        print(f"HBM OVERFLOW: {bad}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
