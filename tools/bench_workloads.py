"""Secondary workload benchmarks on the current backend (TPU by default).

The driver's headline bench (bench.py) is ResNet-50; this tool covers
the other BASELINE-class workloads and the custom kernels, one JSON
line per subcommand (ref: example/image-classification/
benchmark_score.py + tools/bandwidth/measure.py roles):

  python tools/bench_workloads.py bert         # BERT-base MLM train step
  python tools/bench_workloads.py transformer  # Transformer-big WMT14 step
  python tools/bench_workloads.py deepar       # DeepAR forecasting step
  python tools/bench_workloads.py attention    # pallas flash vs XLA sdpa
  python tools/bench_workloads.py rnn          # pallas LSTM vs lax.scan
  python tools/bench_workloads.py all
"""
import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _setup_jax():
    import jax

    from mxnet_tpu.utils import compile_cache

    compile_cache.enable()
    return jax


def _peak_flops(dev):
    sys.path.insert(0, REPO)
    from bench import _peak_flops as pf

    return pf(dev.device_kind) if dev.platform == "tpu" else None


def _bench_trainer(jax, trainer, x, y, steps, tokens_per_step, metric,
                   extra, analytic_flops=None):
    """Shared harness: warmup, best-of-3 bulk-scan timing, FLOPs via
    cost analysis, chip-aggregated MFU, one JSON line. `extra` keys
    override the defaults (e.g. a different "unit").
    `analytic_flops`: per-step fallback when the HLO cost analysis
    can't see the work (lax.scan bodies — the LSTM recurrence — report
    ~0 flops), so scan-dominated models still get an MFU."""
    trainer.step(x, y).wait_to_read()
    trainer.step_many(x, y, n_steps=steps).asnumpy()  # compile scan
    dt = None
    for _ in range(3):
        t0 = time.perf_counter()
        losses = trainer.step_many(x, y, n_steps=steps)
        losses.asnumpy()
        w = time.perf_counter() - t0
        dt = w if dt is None or w < dt else dt

    dev = jax.devices()[0]
    # shared cost machinery with bench.py: compiled post-fusion cost
    # analysis on TPU (real HBM traffic -> roofline bound), HLO-level
    # lowering off-TPU
    from bench import _roofline_bound, _step_cost

    flops, nbytes = _step_cost(trainer, x, y,
                               allow_compile=(dev.platform != "cpu"))
    if (not flops or flops < 1e6) and analytic_flops:
        flops = analytic_flops
    # cost_analysis FLOPs cover the GLOBAL batch over the dp mesh, so
    # peak must aggregate every chip the step ran on (as bench.py does)
    chip_peak = _peak_flops(dev)
    n_chips = len(trainer.mesh.devices.flat)
    peak = chip_peak * n_chips if chip_peak else None
    mfu = (flops * steps / dt / peak) if (flops and peak) else None
    print(json.dumps(dict({
        "metric": metric, "value": round(steps * tokens_per_step / dt),
        "unit": "tokens/sec", "mfu": round(mfu, 4) if mfu else None,
        "roofline_mfu_bound": _roofline_bound(flops, nbytes, dev),
        "device_kind": dev.device_kind, "platform": dev.platform,
        "final_loss": round(float(losses.asnumpy()[-1]), 4)}, **extra)))


class _Identity:
    """Loss adapter for nets whose forward already returns the loss."""

    def __call__(self, out, _):
        return out


def bench_bert(bs=None, seq_len=128, steps=20):
    """BERT-base MLM+NSP training step (BASELINE config #3)."""
    jax = _setup_jax()
    bs = bs if bs is not None else (
        64 if jax.devices()[0].platform == "tpu" else 32)
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.models import bert as bert_mod
    from mxnet_tpu.parallel import data_parallel

    sys.path.insert(0, os.path.join(REPO, "examples", "bert"))
    sys.path.insert(0, os.path.join(REPO, "examples"))
    from pretrain_bert import BERTForPretrain, synthetic_batch

    mx.random.seed(0)
    rng = np.random.RandomState(0)
    vocab = 30522
    model = bert_mod.bert_base(vocab_size=vocab)
    net = BERTForPretrain(model, vocab)
    net.initialize(mx.init.Xavier())

    trainer = data_parallel.DataParallelTrainer(
        net, _Identity(), "adamw", {"learning_rate": 1e-4, "wd": 0.01},
        compute_dtype="bfloat16")
    x = synthetic_batch(rng, bs, seq_len, vocab)
    y = np.zeros((bs,), np.float32)  # unused by the loss head
    _bench_trainer(jax, trainer, x, y, steps, bs * seq_len,
                   "bert_base_mlm_throughput",
                   {"batch_size": bs, "seq_len": seq_len})


def bench_transformer(bs=None, seq_len=None, steps=20, model="big"):
    """Transformer-{base,big} WMT14-style train step (BASELINE #4).

    TPU default bs 64 x seq 64 (preflight: static tier 4.9 GB of
    16 GB, so utilization not memory binds); CPU stays tiny."""
    jax = _setup_jax()
    on_tpu = jax.devices()[0].platform == "tpu"
    bs = bs if bs is not None else (64 if on_tpu else 32)
    seq_len = seq_len if seq_len is not None else (64 if on_tpu else 32)
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.models import transformer as tfm
    from mxnet_tpu.parallel import data_parallel

    sys.path.insert(0, os.path.join(REPO, "examples", "nmt"))
    sys.path.insert(0, os.path.join(REPO, "examples"))
    from train_transformer import (LabelSmoothedCE, Seq2SeqTrainNet,
                                   synthetic_pairs)

    mx.random.seed(0)
    rng = np.random.RandomState(0)
    vocab = 32000
    net = Seq2SeqTrainNet(getattr(tfm, f"transformer_{model}")(vocab,
                                                               vocab))
    net.initialize(mx.init.Xavier())
    trainer = data_parallel.DataParallelTrainer(
        net, LabelSmoothedCE(), "adam",
        {"learning_rate": 3e-4, "beta2": 0.98},
        compute_dtype="bfloat16")
    src, tgt_in, tgt_out = synthetic_pairs(rng, bs, seq_len, vocab)
    _bench_trainer(jax, trainer, (src, tgt_in), tgt_out, steps,
                   bs * seq_len,
                   f"transformer_{model}_train_throughput",
                   {"batch_size": bs, "seq_len": seq_len})


def bench_deepar(bs=64, context_length=72, prediction_length=24,
                 steps=20, num_cells=40, num_layers=2):
    """DeepAR probabilistic-forecasting train step (BASELINE #5)."""
    jax = _setup_jax()
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import models
    from mxnet_tpu.parallel import data_parallel

    sys.path.insert(0, os.path.join(REPO, "examples", "forecasting"))
    sys.path.insert(0, os.path.join(REPO, "examples"))
    from train_deepar import synthetic_series

    mx.random.seed(0)
    rng = np.random.RandomState(0)
    net = models.deepar(num_cells, num_layers)
    net.initialize(mx.init.Xavier())
    trainer = data_parallel.DataParallelTrainer(
        net, _Identity(), "adam", {"learning_rate": 1e-3})
    T = context_length + prediction_length
    x = synthetic_series(rng, bs, T).astype(np.float32)
    y = np.zeros((bs,), np.float32)  # unused by the NLL head
    # scan bodies report ~0 flops to the HLO cost analysis; analytic
    # LSTM count instead: per step/sample/layer one (4H,in)+(4H,H)
    # GEMM pair (2 flops/MAC), training ~= 3x forward
    H = num_cells
    in_sizes = [x.shape[-1] if x.ndim == 3 else 1] + \
        [H] * (num_layers - 1)
    fwd = sum(2 * 4 * H * (i + H) for i in in_sizes) * T * bs
    _bench_trainer(jax, trainer, x, y, steps, bs * T,
                   "deepar_train_throughput",
                   {"batch_size": bs, "series_length": T,
                    "unit": "series points/sec"},
                   analytic_flops=3.0 * fwd)


def bench_attention(bs=8, heads=16, seq=2048, hd=64, iters=20):
    """Pallas flash attention vs the XLA reference sdpa (fwd+bwd)."""
    jax = _setup_jax()
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.ops.attention import sdpa_reference
    from mxnet_tpu.ops.pallas import flash_attention as fa

    rng = np.random.RandomState(0)
    shape = (bs, heads, seq, hd)
    q, k, v = (jnp.asarray(rng.randn(*shape).astype(np.float32),
                           jnp.bfloat16) for _ in range(3))

    def time_fn(f):
        g = jax.jit(jax.grad(lambda q, k, v:
                             jnp.sum(f(q, k, v).astype(jnp.float32)),
                             argnums=(0, 1, 2)))
        g(q, k, v)[0].block_until_ready()  # compile
        best = None
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(iters):
                out = g(q, k, v)
            out[0].block_until_ready()
            w = (time.perf_counter() - t0) / iters
            best = w if best is None or w < best else best
        return best

    t_flash = time_fn(lambda q, k, v: fa.flash_attention(q, k, v,
                                                         causal=True))
    t_ref = time_fn(lambda q, k, v: sdpa_reference(q, k, v, causal=True))
    dev = jax.devices()[0]
    print(json.dumps({
        "metric": "flash_attention_fwdbwd_ms",
        "value": round(t_flash * 1e3, 3), "unit": "ms",
        "xla_reference_ms": round(t_ref * 1e3, 3),
        "speedup_vs_xla": round(t_ref / t_flash, 3),
        "shape": list(shape), "causal": True,
        "device_kind": dev.device_kind, "platform": dev.platform}))


def bench_rnn(bs=64, seq=256, input_size=512, hidden=512, iters=10):
    """Fused Pallas LSTM vs the lax.scan path (fwd only, inference)."""
    jax = _setup_jax()
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.ops import rnn as rnn_ops

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(seq, bs, input_size).astype(np.float32))
    params = jnp.asarray(rng.randn(
        rnn_ops.rnn_param_size(1, input_size, hidden, "lstm"))
        .astype(np.float32) * 0.05)
    h0 = jnp.zeros((1, bs, hidden), jnp.float32)
    c0 = jnp.zeros((1, bs, hidden), jnp.float32)

    def time_mode(use_pallas):
        os.environ["MXTPU_RNN_IMPL"] = "pallas" if use_pallas else "scan"
        fn = jax.jit(lambda x, p, h, c: rnn_ops._k_rnn(
            x, p, h, c, state_size=hidden, num_layers=1,
            mode="lstm", state_outputs=True)[0])
        fn(x, params, h0, c0).block_until_ready()
        best = None
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(iters):
                out = fn(x, params, h0, c0)
            out.block_until_ready()
            w = (time.perf_counter() - t0) / iters
            best = w if best is None or w < best else best
        return best

    try:
        t_pallas = time_mode(True)
        t_scan = time_mode(False)
    finally:
        os.environ.pop("MXTPU_RNN_IMPL", None)
    dev = jax.devices()[0]
    print(json.dumps({
        "metric": "lstm_fwd_ms", "value": round(t_pallas * 1e3, 3),
        "unit": "ms", "lax_scan_ms": round(t_scan * 1e3, 3),
        "speedup_vs_scan": round(t_scan / t_pallas, 3),
        "shape": [seq, bs, input_size], "hidden": hidden,
        "device_kind": dev.device_kind, "platform": dev.platform}))


def bench_convfuse(bs=128, image=224, steps=20):
    """ResNet-50 NHWC bf16 train step, standard XLA path vs the
    MXTPU_CONV_EPILOGUE=pallas fused conv1x1+BN+ReLU path (VERDICT r2
    #2: the epilogue fusion the roofline analysis calls for).  Emits
    one JSON line per mode; the A/B delta is the fusion's measured
    value on this chip."""
    import os

    jax = _setup_jax()
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.parallel import data_parallel

    x = np.random.RandomState(0).rand(bs, image, image, 3) \
        .astype(np.float32)
    y = np.random.RandomState(1).randint(0, 1000, bs).astype(np.float32)
    prev_epilogue = os.environ.get("MXTPU_CONV_EPILOGUE")
    try:
        for mode in ("xla", "pallas"):
            os.environ["MXTPU_CONV_EPILOGUE"] = \
                "" if mode == "xla" else "pallas"
            from mxnet_tpu.gluon.model_zoo import vision

            mx.random.seed(0)
            net = vision.resnet50_v1(layout="NHWC")
            net.initialize(mx.init.Xavier())
            trainer = data_parallel.DataParallelTrainer(
                net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
                {"learning_rate": 0.1, "momentum": 0.9},
                compute_dtype="bfloat16")
            _bench_trainer(jax, trainer, x, y, steps, bs,
                           f"resnet50_convfuse_{mode}",
                           {"unit": "images/sec", "batch_size": bs,
                            "image_size": image, "conv_epilogue": mode})
    finally:
        if prev_epilogue is None:
            os.environ.pop("MXTPU_CONV_EPILOGUE", None)
        else:
            os.environ["MXTPU_CONV_EPILOGUE"] = prev_epilogue


def bench_quantized(bs=64, image=224, steps=20, network="resnet50_v1"):
    """INT8 vs fp32 inference throughput on a model-zoo CNN — the
    fork's specialty workload (ref: the ykim362 fork's MKL-DNN INT8
    quantization tier; here int8 rides lax.dot_general int8 kernels,
    SURVEY §2.2 quantization row).  Exports the gluon net to
    symbol+params, quantizes FC/Conv to int8 via
    contrib.quantization.quantize_model, and times executor forward
    for both graphs.  Emits one JSON line per precision; the A/B delta
    is the int8 speedup on this chip."""
    import tempfile
    import time as _time

    jax = _setup_jax()
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu import symbol as sym_mod
    from mxnet_tpu.contrib import quantization as qz
    from mxnet_tpu.gluon.model_zoo import vision

    mx.random.seed(0)
    net = getattr(vision, network)()
    net.initialize(mx.init.Xavier())
    net.hybridize()
    x_np = np.random.RandomState(0).rand(bs, 3, image, image) \
        .astype(np.float32)
    net(nd.array(x_np[:2]))  # build params
    tmp = tempfile.mkdtemp(prefix="mxtpu_qbench_")
    prefix = os.path.join(tmp, "net")
    net.export(prefix)
    symbol = sym_mod.load(prefix + "-symbol.json")
    payload = nd.load(prefix + "-0000.params")
    arg_params = {k[4:]: v for k, v in payload.items()
                  if k.startswith("arg:")}
    aux_params = {k[4:]: v for k, v in payload.items()
                  if k.startswith("aux:")}

    qsym, qargs, qaux = qz.quantize_model(
        symbol, arg_params, aux_params, calib_mode="naive",
        calib_data=x_np[: min(bs, 8)])

    dev = jax.devices()[0]
    x = nd.array(x_np)
    for mode, s, a, aux in (("fp32", symbol, arg_params, aux_params),
                            ("int8", qsym, qargs, qaux)):
        ex = s.bind(mx.current_context(), dict(a, data=x),
                    grad_req="null", aux_states=dict(aux))
        ex.forward(is_train=False)[0].wait_to_read()  # compile
        best = None
        for _ in range(3):
            t0 = _time.perf_counter()
            for _ in range(steps):
                out = ex.forward(is_train=False)[0]
            out.wait_to_read()
            w = (_time.perf_counter() - t0) / steps
            best = w if best is None or w < best else best
        print(json.dumps({
            "metric": f"{network}_infer_{mode}",
            "value": round(bs / best, 2), "unit": "images/sec",
            "batch_size": bs, "image_size": image, "network": network,
            "device_kind": dev.device_kind, "platform": dev.platform}))


def bench_io(n_images=2048, size=256, batch_size=128, data_shape=96,
             threads=None):
    """Decode throughput through the native pipeline: JPEG .rec ->
    src/recordio.cc decode/augment threads -> batches (VERDICT r2 #3;
    ref: iter_image_recordio_2.cc, SURVEY §3.5 ~10k img/s target for
    the ResNet-50 hot loop).  Generates a synthetic JPEG dataset in a
    temp dir, then measures steady-state img/s for the native C++
    pipeline and the pure-Python fallback."""
    import shutil
    import tempfile

    import numpy as np

    from mxnet_tpu.io import ImageRecordIter, recordio

    threads = threads or (os.cpu_count() or 4)
    tmp = tempfile.mkdtemp(prefix="mxtpu_iobench_")
    rec = os.path.join(tmp, "bench.rec")
    idx = os.path.join(tmp, "bench.idx")
    try:
        rng = np.random.RandomState(0)
        w = recordio.MXIndexedRecordIO(idx, rec, "w")
        # realistic JPEG entropy: smooth gradients + noise, not white
        # noise (which decodes unusually slowly) or flat color (fast)
        base = rng.rand(size, size, 3) * 255
        for i in range(n_images):
            img = np.clip(base + rng.rand(size, size, 3) * 64 - 32,
                          0, 255).astype(np.uint8)
            w.write_idx(i, recordio.pack_img(
                recordio.IRHeader(0, float(i % 1000), i, 0), img,
                quality=85))
        w.close()

        for use_native in (True, False):
            it = ImageRecordIter(
                path_imgrec=rec, data_shape=(3, data_shape, data_shape),
                batch_size=batch_size, shuffle=True, rand_crop=True,
                rand_mirror=True, preprocess_threads=threads,
                use_native=use_native)
            n = sum(b.data[0].shape[0] for b in it)  # warm epoch
            it.reset()
            t0 = time.perf_counter()
            n = sum(b.data[0].shape[0] for b in it)
            dt = time.perf_counter() - t0
            print(json.dumps({
                "metric": "imagerecorditer_decode_throughput",
                "value": round(n / dt, 1), "unit": "images/sec",
                "pipeline": "native" if use_native else "python",
                "n_images": n, "src_size": size,
                "data_shape": data_shape, "batch_size": batch_size,
                "threads": threads}))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("which", choices=["bert", "transformer", "deepar",
                                     "attention", "rnn", "convfuse",
                                     "quantized", "io", "all"])
    p.add_argument("--batch-size", type=int, default=None,
                   help="override the per-benchmark default batch size")
    p.add_argument("--model", default="big", choices=["base", "big"],
                   help="transformer variant (transformer subcommand)")
    p.add_argument("--network", default="resnet50_v1",
                   help="model-zoo CNN for the quantized A/B")
    p.add_argument("--image-size", type=int, default=224,
                   help="input resolution for the quantized A/B")
    p.add_argument("--steps", type=int, default=20,
                   help="timed steps for the quantized A/B")
    args = p.parse_args()
    bs_kw = {"bs": args.batch_size} if args.batch_size else {}
    if args.which in ("bert", "all"):
        bench_bert(**bs_kw)
    if args.which in ("transformer", "all"):
        bench_transformer(model=args.model, **bs_kw)
    if args.which in ("deepar", "all"):
        bench_deepar(**bs_kw)
    if args.which in ("attention", "all"):
        bench_attention(**bs_kw)
    if args.which in ("rnn", "all"):
        bench_rnn(**bs_kw)
    if args.which in ("convfuse", "all"):
        bench_convfuse(**bs_kw)
    if args.which in ("quantized", "all"):
        bench_quantized(network=args.network, image=args.image_size,
                        steps=args.steps, **bs_kw)
    if args.which in ("io", "all"):
        bench_io(batch_size=args.batch_size or 128)


if __name__ == "__main__":
    main()
