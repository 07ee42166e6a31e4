"""mxnet_tpu.serve.decode — continuous batching over a slot arena.

Covers the decode tier's contract: continuously-batched decode is
bit-identical to sequential whole-batch decode of the same prompts
(slot reuse and co-resident churn never leak across rows); a warmed
server takes a staggered mixed stream with ZERO new XLA compilations
and exact dispatch accounting (one per token step, one per prefill
group, one per admission); deadlines expire mid-decode and free the
slot immediately; drain leaves zero live slots; hot reload swaps
weights mid-stream without a recompile; and the concurrent stress run
holds under the runtime lock-order checker.
"""
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import _imperative, checkpoint, serve

VOCAB = 64


def _make_model(seed=3, vocab=VOCAB, embed=16):
    mx.random.seed(seed)
    model = serve.TinyDecoder(vocab=vocab, embed=embed)
    model.initialize(mx.init.Xavier())
    return model


def _spec(batches=(1, 2, 4), lengths=(4, 8)):
    return serve.BucketSpec(batch_sizes=batches, example_shape=(None,),
                            lengths=lengths, dtype="int32")


def _prompts(n, rng, max_len=8):
    return [rng.randint(0, VOCAB, size=int(rng.randint(2, max_len + 1)))
            .astype(np.int32) for _ in range(n)]


def _server(model, **kwargs):
    kwargs.setdefault("max_slots", 4)
    kwargs.setdefault("max_len", 32)
    return serve.DecodeServer(model, kwargs.pop("spec", _spec()), **kwargs)


# ---------------------------------------------------------------------------
# parity: the acceptance gate


def test_parity_continuous_vs_whole_batch_decode():
    """Continuously-batched outputs are bit-identical to sequential
    whole-batch decode of the same prompts: staggered admission, slot
    reuse, and different co-residents never change any sequence."""
    model = _make_model()
    rng = np.random.RandomState(1)
    prompts = _prompts(14, rng)
    budgets = [int(rng.randint(2, 12)) for _ in prompts]

    def run(admission, stagger=0.0):
        srv = _server(model, admission=admission)
        srv.start()
        handles = []
        for p, m in zip(prompts, budgets):
            handles.append(srv.submit(p, max_new_tokens=m))
            if stagger:
                time.sleep(stagger)
        seqs = [h.result(timeout=120) for h in handles]
        srv.drain()
        return seqs, srv.stats()

    cont, s_cont = run("continuous", stagger=0.002)
    whole, s_whole = run("batch")
    for a, b in zip(cont, whole):
        np.testing.assert_array_equal(a, b)
    assert all(len(seq) == m for seq, m in zip(cont, budgets))
    # (the scheduling win itself — fewer step dispatches per token —
    # is asserted under saturated load in
    # test_staggered_admission_zero_compiles_exact_dispatches; at this
    # trickle rate the arena runs far below capacity and step counts
    # are arrival-bound)
    assert s_cont["graph"]["post_warmup_compiles"] == 0
    assert s_whole["graph"]["post_warmup_compiles"] == 0


# ---------------------------------------------------------------------------
# closed compile surface + honest dispatch accounting


def test_staggered_admission_zero_compiles_exact_dispatches():
    model = _make_model()
    srv = _server(model, max_queue=128)
    srv.start()
    execs_before = _imperative.compiled_executable_count()
    d0 = _imperative.device_dispatch_count()
    rng = np.random.RandomState(2)
    handles = []
    for i, p in enumerate(_prompts(24, rng)):
        handles.append(srv.submit(p, max_new_tokens=int(rng.randint(1, 9))))
        if i % 4 == 0:
            time.sleep(0.002)
    for h in handles:
        h.result(timeout=120)
    srv.drain()
    d1 = _imperative.device_dispatch_count()
    s = srv.stats()
    assert s["served"] == 24
    assert s["graph"]["post_warmup_compiles"] == 0
    assert _imperative.compiled_executable_count() == execs_before
    # the honest counter: one dispatch per token step, one per fused
    # prefill+write admission group — nothing eager leaks into the loop
    assert d1 - d0 == s["decode_steps"] + s["batches"]
    assert s["admitted"] == 24
    # iteration-level scheduling: many tokens ride each step dispatch
    assert s["tokens"] > s["decode_steps"]


def test_single_sequence_one_dispatch_per_token():
    """Steady state with one live sequence: exactly 1 device dispatch
    per generated token (after the admission prefill+write)."""
    model = _make_model()
    srv = _server(model)
    srv.start()
    rng = np.random.RandomState(3)
    h = srv.submit(_prompts(1, rng)[0], max_new_tokens=9)
    seq = h.result(timeout=120)
    srv.drain()
    s = srv.stats()
    assert len(seq) == 9
    # first token comes from prefill; each later token is ONE step
    assert s["decode_steps"] == 8
    assert s["batches"] == 1
    assert s["graph"]["post_warmup_compiles"] == 0


def test_eos_terminates_early_and_frees_slot():
    model = _make_model()
    srv = _server(model)
    srv.start()
    rng = np.random.RandomState(12)
    prompt = _prompts(1, rng)[0]
    ref = srv.generate(prompt, max_new_tokens=10, timeout=120)
    srv.drain()
    # pick a token the greedy sequence provably emits; a server with
    # that eos_id must stop at its first occurrence
    eos = int(ref[3])
    first_idx = int(np.argmax(ref == eos))
    srv2 = _server(model, eos_id=eos)
    srv2.start()
    seq = srv2.generate(prompt, max_new_tokens=10, timeout=120)
    srv2.drain()
    np.testing.assert_array_equal(seq, ref[:first_idx + 1])
    s = srv2.stats()
    assert s["served"] == 1 and s["slots"]["live"] == 0


# ---------------------------------------------------------------------------
# streaming


def test_stream_iterator_matches_future():
    model = _make_model()
    srv = _server(model)
    srv.start()
    rng = np.random.RandomState(4)
    h = srv.submit(_prompts(1, rng)[0], max_new_tokens=7)
    streamed = list(h)
    assert streamed == list(h.result(timeout=120))
    assert len(streamed) == 7
    # a second pass over the handle terminates (sentinel stays put)
    assert list(h) == []
    srv.drain()


# ---------------------------------------------------------------------------
# deadlines / cancellation free slots mid-decode


def test_mid_decode_deadline_frees_slot():
    model = _make_model()
    srv = _server(model)
    srv.start()
    rng = np.random.RandomState(5)
    # a generous budget that cannot finish inside the deadline: the
    # deadline check at a token boundary must fail it and free the slot
    doomed = srv.submit(_prompts(1, rng)[0], max_new_tokens=24,
                        deadline_ms=1)
    time.sleep(0.05)
    with pytest.raises(serve.DeadlineExceededError):
        doomed.result(timeout=120)
    # the freed slot keeps serving new traffic
    ok = srv.submit(_prompts(1, rng)[0], max_new_tokens=4)
    assert len(ok.result(timeout=120)) == 4
    srv.drain()
    s = srv.stats()
    assert s["expired_deadline"] == 1 and s["served"] == 1
    assert s["slots"]["live"] == 0
    assert s["submitted"] == s["served"] + s["expired_deadline"]
    # the stream carries the same terminal error
    with pytest.raises(serve.DeadlineExceededError):
        list(doomed)


def test_cancel_frees_slot_and_voids_queued():
    model = _make_model()
    srv = _server(model, max_slots=1, max_len=2048)
    srv.start()
    rng = np.random.RandomState(6)
    live = srv.submit(_prompts(1, rng)[0], max_new_tokens=2000)
    queued = srv.submit(_prompts(1, rng)[0], max_new_tokens=2000)
    time.sleep(0.02)          # let the first admit and start decoding
    live.cancel()
    queued.cancel()
    srv.drain()
    s = srv.stats()
    assert s["cancelled"] == 2 and s["served"] == 0
    assert s["slots"]["live"] == 0 and s["queue_depth"] == 0


# ---------------------------------------------------------------------------
# drain / restart


def test_drain_leaves_zero_live_slots_and_restarts_warm():
    model = _make_model()
    srv = _server(model)
    srv.start()
    rng = np.random.RandomState(7)
    handles = [srv.submit(p, max_new_tokens=5)
               for p in _prompts(10, rng)]
    srv.drain()
    assert all(h.future.done() for h in handles)
    s = srv.stats()
    assert s["served"] == s["submitted"] == 10
    assert s["queue_depth"] == 0 and s["slots"]["live"] == 0
    with pytest.raises(serve.ServerClosedError):
        srv.submit(_prompts(1, rng)[0])
    # restart reuses every warmed executable: zero new compiles
    srv.start()
    assert len(srv.generate(_prompts(1, rng)[0], max_new_tokens=3,
                            timeout=120)) == 3
    srv.drain()
    assert srv.stats()["graph"]["post_warmup_compiles"] == 0


def test_overload_rejection_and_backpressure():
    model = _make_model()
    srv = _server(model, max_slots=1, max_queue=2)
    srv.start()
    rng = np.random.RandomState(8)
    handles, rejected = [], 0
    for p in _prompts(12, rng):
        try:
            handles.append(srv.submit(p, max_new_tokens=12))
        except serve.ServerOverloadedError:
            rejected += 1
    assert rejected > 0       # the bounded admission queue sheds load
    for h in handles:
        h.result(timeout=300)
    srv.drain()
    s = srv.stats()
    assert s["rejected_overload"] == rejected
    assert s["served"] == s["submitted"] == 12 - rejected


# ---------------------------------------------------------------------------
# hot reload mid-stream


def test_hot_reload_mid_stream(tmp_path):
    trained = _make_model(seed=11)
    mgr = checkpoint.CheckpointManager(str(tmp_path))
    mgr.save(7, params=trained, sync=True)
    mgr.wait_until_finished()

    serving = _make_model(seed=99)    # same arch, different weights
    srv = _server(serving, checkpoint=str(tmp_path))
    srv.start()
    rng = np.random.RandomState(9)
    prompt = _prompts(1, rng)[0]
    before = srv.generate(prompt, max_new_tokens=6, timeout=120)
    # reload between token boundaries of a LIVE stream: the sequence
    # finishes (on swapped weights), nothing drops, nothing recompiles
    mid = srv.submit(prompt, max_new_tokens=20)
    meta = srv.reload_weights()
    assert len(mid.result(timeout=120)) == 20
    after = srv.generate(prompt, max_new_tokens=6, timeout=120)
    srv.drain()
    assert meta["step"] == 7
    s = srv.stats()
    assert s["reloads"] == 1
    assert s["graph"]["post_warmup_compiles"] == 0
    # post-reload output equals a server built on the trained weights
    ref_srv = _server(trained)
    ref_srv.start()
    ref = ref_srv.generate(prompt, max_new_tokens=6, timeout=120)
    ref_srv.drain()
    np.testing.assert_array_equal(after, ref)
    assert before.shape == after.shape


# ---------------------------------------------------------------------------
# failure injection: the loop survives, the arena resets


def test_injected_step_fault_fails_live_and_keeps_serving():
    from mxnet_tpu.resilience import faults

    model = _make_model()
    srv = _server(model)
    srv.start()
    rng = np.random.RandomState(10)
    plan = faults.FaultPlan([{"site": "serve.decode", "action": "raise",
                              "on_hit": 2}])
    with faults.armed(plan):
        doomed = srv.submit(_prompts(1, rng)[0], max_new_tokens=24)
        with pytest.raises(faults.TransientFault):
            doomed.result(timeout=120)
    # the loop thread survived: fresh traffic decodes normally
    assert len(srv.generate(_prompts(1, rng)[0], max_new_tokens=5,
                            timeout=120)) == 5
    srv.drain()
    s = srv.stats()
    assert s["failed"] == 1 and s["served"] == 1
    assert s["slots"]["live"] == 0


# ---------------------------------------------------------------------------
# profiler section + request spans


def test_decode_serve_section_and_request_spans(tmp_path):
    import json

    from mxnet_tpu import profiler, telemetry
    from mxnet_tpu.serve import decode as decode_mod

    decode_mod.reset_decode_serve_stats()
    model = _make_model()
    srv = _server(model)
    srv.start()
    rng = np.random.RandomState(11)
    trace_path = str(tmp_path / "decode.trace.json")
    with telemetry.trace(trace_path):
        handles = [srv.submit(p, max_new_tokens=4)
                   for p in _prompts(6, rng)]
        for h in handles:
            h.result(timeout=120)
    srv.drain()

    section = json.loads(profiler.dumps(reset=True))["decodeServe"]
    assert section["admitted"] == section["finished"] == 6
    assert section["tokens"] == 24
    assert section["steps"] >= 3
    assert 0 < section["slot_occupancy"] <= 1
    # window-scoped: the reset dump rewound the section
    fresh = json.loads(profiler.dumps())["decodeServe"]
    assert fresh["tokens"] == fresh["admitted"] == 0

    events = json.load(open(trace_path))["traceEvents"]
    begins = [e for e in events if e["ph"] == "b"
              and e["name"] == "serve.decode.request"]
    ends = [e for e in events if e["ph"] == "e"
            and e["name"] == "serve.decode.request"]
    assert len(begins) == len(ends) == 6
    assert {e["id"] for e in begins} == {e["id"] for e in ends}
    assert all("prompt_len" in e["args"] for e in begins)
    for e in ends:
        assert e["args"]["outcome"] == "served"
        assert e["args"]["tokens"] == 4
        assert e["args"]["queue_ms"] >= 0
        assert e["args"]["decode_ms"] >= 0
    firsts = [e for e in events if e["ph"] == "n"
              and e["name"] == "serve.decode.first_token"]
    assert len(firsts) == 6 and all(e["args"]["ttft_ms"] > 0
                                    for e in firsts)
    names = {e["name"] for e in events}
    assert {"serve.prefill", "serve.decode.admit",
            "serve.decode.step"} <= names


# ---------------------------------------------------------------------------
# concurrent stress under the runtime lock checker


@pytest.mark.slow
def test_decode_stress_concurrent_submitters():
    """Many concurrent submitters + a mid-stream hot reload against the
    decode loop: every accepted request resolves with its full budget,
    the accounting invariant holds, the compile surface stays closed,
    and the lock-order checker observes zero inversions across the
    batcher/stats/exec-lock nest."""
    from mxnet_tpu.analysis import runtime as lock_order

    lock_order.reset()
    assert lock_order.enable(raise_on_inversion=False), \
        "lock-order checker was already on"
    lock_order.wrap_existing()
    try:
        _decode_stress_body()
    finally:
        lock_order.disable()
        lock_order.unwrap_existing()
    assert lock_order.inversions() == []
    assert lock_order.stats()["acquires"] > 0


def _decode_stress_body():
    model = _make_model()
    srv = _server(model, max_slots=8, max_queue=512)
    srv.start()
    n_threads, per_thread = 6, 25
    results, errors = [], []
    lock = threading.Lock()

    def submitter(seed):
        rng = np.random.RandomState(seed)
        handles = [srv.submit(p, max_new_tokens=int(rng.randint(1, 9)))
                   for p in _prompts(per_thread, rng)]
        for h in handles:
            try:
                r = h.result(timeout=600)
                with lock:
                    results.append(r)
            except Exception as e:  # noqa: BLE001 — collected for assert
                with lock:
                    errors.append(e)

    threads = [threading.Thread(target=submitter, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    srv.drain()
    s = srv.stats()
    assert not errors
    assert len(results) == n_threads * per_thread
    assert s["served"] == s["submitted"] == n_threads * per_thread
    assert s["slots"]["live"] == 0 and s["queue_depth"] == 0
    assert s["graph"]["post_warmup_compiles"] == 0
    assert s["tokens"] > s["decode_steps"]  # real continuous batching
