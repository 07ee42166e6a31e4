"""mxnet_tpu.serve.control_plane — the cross-process serving tier.

Covers ISSUE 19's contract: the MXRP frame codec round-trips tensors
and rejects newer-wire frames loudly; a RemoteReplica is
bit-identical to the in-process server it fronts; a mid-stream
connection kill (injected at the cataloged ``serve.rpc.send`` fault
point) fails over through the router's existing re-dispatch path with
the token stream intact; a slow stream consumer never head-of-line
blocks other requests on the shared connection; the autoscaler's
hysteresis, cooldown and bounds; spawn failures and wire errors land
in the retryable classification classes; stale registry leases are
rejected; and the router's ``requests_lost`` audit stays exactly 0
across a connection kill.

All tier-1 tests run in ONE process over real localhost sockets (the
actual 3-subprocess chaos gate lives in ``tools/ctrl_smoke.py``).
"""
import os
import struct
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import base, serve
from mxnet_tpu.parallel.dist import LeaseDir
from mxnet_tpu.resilience import faults
from mxnet_tpu.resilience.supervisor import classify
from mxnet_tpu.serve import control_plane as cp
from mxnet_tpu.serve.control_plane.rpc import (RPCConnectionError,
                                               WIRE_MAGIC, WIRE_VERSION)

VOCAB = 32


def _decode_server(seed=4):
    mx.random.seed(seed)
    model = serve.TinyDecoder(vocab=VOCAB, embed=8)
    model.initialize(mx.init.Xavier())
    spec = serve.BucketSpec(batch_sizes=(1, 2), example_shape=(None,),
                            lengths=(4, 8), dtype="int32")
    srv = serve.DecodeServer(model, spec, max_slots=2, max_len=16)
    srv.start()
    return srv


@pytest.fixture(scope="module")
def decode_pair():
    """Two warmed same-seed decode servers behind endpoints — the
    bit-identical replica pool every cross-process test rides.  Tests
    must NOT shut the routers down (that would shut down the shared
    servers through the wire); they drop their client connections
    instead, and build their routers with `_router`."""
    pair = []
    for _ in range(2):
        srv = _decode_server(seed=4)
        pair.append((srv, cp.serve_replica(srv)))
    yield pair
    for srv, ep in pair:
        ep.stop()
        srv.shutdown(drain=False)


def _remotes(decode_pair):
    return [cp.RemoteReplica(ep.host, ep.port, rid=i)
            for i, (_, ep) in enumerate(decode_pair)]


def _drop(replicas):
    for rr in replicas:
        rr._teardown(RPCConnectionError("test teardown"))


def _router(replicas):
    """A router over the SHARED pair.  An evicted `RemoteReplica` is
    shut down through the wire, over a fresh connection, and that stops
    the module's server for every later test (`ServerClosedError` in
    `test_decode_handle_sink_replays_history`, whenever a cut wire
    found three requests in flight on its replica): no burst here may
    reach the eviction count."""
    router = serve.Router(servers=replicas, health_sec=0.0,
                          evict_after=10 ** 6)
    router.start()
    return router


# ---------------------------------------------------------------------------
# 1. wire codec


def test_wire_roundtrip_and_version_mismatch():
    import socket

    a, b = socket.socketpair()
    try:
        meta = {"op": "x", "rid": 3, "kwargs": {"k": 1}}
        arrays = {"t": np.arange(6, dtype=np.int32).reshape(2, 3),
                  "f": np.linspace(0, 1, 4, dtype=np.float32)}
        cp.send_frame(a, meta, arrays)
        got_meta, got = cp.recv_frame(b)
        assert got_meta == meta
        for k in arrays:
            assert got[k].dtype == arrays[k].dtype
            assert np.array_equal(got[k], arrays[k])

        # payload-less frame
        cp.send_frame(a, {"op": "ping"})
        assert cp.recv_frame(b) == ({"op": "ping"}, None)

        # a frame stamped by a NEWER build is rejected with a
        # diagnosis, never misparsed
        hdr = struct.Struct("<HIQ")
        a.sendall(WIRE_MAGIC + hdr.pack(WIRE_VERSION + 7, 2, 0) + b"{}")
        with pytest.raises(mx.MXNetError, match="newer mxnet_tpu"):
            cp.recv_frame(b)

        # bad magic: not our protocol at all
        a.sendall(b"HTTP" + b"\x00" * hdr.size)
        with pytest.raises(mx.MXNetError, match="bad magic"):
            cp.recv_frame(b)
    finally:
        a.close()
        b.close()


def test_truncated_frame_classifies_network_not_corrupt():
    """A connection dying MID-FRAME is a transport failure the router
    retries — it must NOT classify like a corrupt checkpoint file even
    though both involve truncation."""
    import socket

    a, b = socket.socketpair()
    try:
        hdr = struct.Struct("<HIQ")
        a.sendall(WIRE_MAGIC + hdr.pack(WIRE_VERSION, 100, 0) + b"{par")
        a.close()
        with pytest.raises(RPCConnectionError, match="truncated frame"):
            cp.recv_frame(b)
        try:
            cp.recv_frame(b)
        except RPCConnectionError as e:
            assert classify(e) == "network"
    finally:
        b.close()
    assert classify(ConnectionResetError("peer reset")) == "network"
    assert classify(ConnectionRefusedError("nope")) == "network"
    assert classify(BrokenPipeError("gone")) == "network"
    # the fatal/corrupt passthrough matrix is untouched
    assert classify(mx.MXNetError(
        "corrupt or truncated NDArray file")) == "corrupt_checkpoint"
    assert classify(ValueError("boom")) == "fatal"


# ---------------------------------------------------------------------------
# 2. remote parity


def test_remote_replica_parity_bit_identical(decode_pair):
    """The SAME request through the wire and in-process returns the
    SAME bytes — RemoteReplica is a transport, not a reinterpretation."""
    srv, _ = decode_pair[0]
    (rr,) = _remotes(decode_pair)[:1]
    rr.start()
    try:
        rng = np.random.RandomState(7)
        for _ in range(3):
            prompt = rng.randint(
                0, VOCAB, size=int(rng.randint(2, 7))).astype(np.int32)
            handle = rr.submit(prompt, max_new_tokens=5)
            toks = list(handle)
            remote = handle.result(timeout=60)
            local = srv.generate(prompt, max_new_tokens=5, timeout=60)
            assert np.array_equal(remote, np.asarray(local))
            assert toks == [int(t) for t in local]
        assert rr.pending() == srv.pending()
        assert np.array_equal(rr.probe_example(), srv.probe_example())
        assert rr.health()["ok"] is True
        assert rr.stats()["admitted"] >= 3
    finally:
        _drop([rr])


def test_remote_model_server_parity():
    from mxnet_tpu.gluon import nn

    mx.random.seed(3)
    net = nn.HybridSequential()
    net.add(nn.Dense(8, flatten=False, in_units=6, activation="relu"),
            nn.Dense(5, flatten=False, in_units=8))
    net.initialize(mx.init.Xavier())
    spec = serve.BucketSpec(batch_sizes=(1, 2),
                            example_shape=(None, 6), lengths=(4, 8))
    srv = serve.ModelServer(net, spec, max_queue=16)
    srv.start()
    ep = cp.serve_replica(srv)
    rr = cp.RemoteReplica(ep.host, ep.port, rid=0)
    try:
        rr.start()
        x = np.random.RandomState(0).rand(4, 6).astype(np.float32)
        remote = rr.submit(x).result(timeout=60)
        local = srv.predict(x, timeout=60)
        assert np.array_equal(remote, np.asarray(local))
    finally:
        _drop([rr])
        ep.stop()
        srv.shutdown(drain=False)


# ---------------------------------------------------------------------------
# 3/4. pooled streaming: failover + no HOL blocking


def test_midstream_connection_kill_fails_over(decode_pair):
    """Kill the serving connection after 2 streamed tokens (injected at
    ``serve.rpc.send``): the router re-dispatches on the other replica
    and the CONSUMER sees one uninterrupted, duplicate-free stream —
    bit-identical to a single-server run."""
    srv0, _ = decode_pair[0]
    replicas = _remotes(decode_pair)
    router = _router(replicas)
    try:
        prompt = np.array([1, 2, 3], np.int32)
        ref = [int(t) for t in srv0.generate(prompt, max_new_tokens=6,
                                             timeout=60)]
        # stall the (in-process) decode loop so the stream is still
        # LIVE when the wire is cut — without it a fast box finishes
        # all 6 tokens before the victim is even picked
        stall = faults.FaultPlan([{"site": "serve.decode",
                                   "action": "stall", "delay_s": 0.05,
                                   "times": None}])
        with faults.armed(stall):
            handle = router.submit_stream(prompt, max_new_tokens=6)
            got = [next(handle), next(handle)]
            # find who is serving the stream, then cut ITS connection
            victim = next(r for r in replicas if r._pending)
            plan = faults.FaultPlan([{"site": "serve.rpc.send",
                                      "action": "raise",
                                      "match": {"replica": victim.rid}}])
            with faults.armed(plan):
                with pytest.raises(mx.MXNetError):
                    victim.ping()   # the send that drops the wire
        assert [f["site"] for f in plan.fired()] == ["serve.rpc.send"]
        assert plan.fired()[0]["ctx"]["replica"] == victim.rid
        got += list(handle)
        assert got == ref                      # no gap, no duplicates
        assert np.array_equal(handle.result(timeout=60),
                              np.asarray(ref, np.int32))
        s = router.stats()
        assert s["retries"] >= 1
        assert s["requests_lost"] == 0
    finally:
        _drop(replicas)


def test_slow_consumer_does_not_block_others(decode_pair):
    """Two streams multiplexed on ONE replica connection: the consumer
    ignoring stream A must not stall stream B's tokens (the demux
    drains the socket unconditionally into per-request queues)."""
    replicas = _remotes(decode_pair)[:1]
    router = _router(replicas)
    try:
        slow = router.submit_stream(np.array([1, 2, 3], np.int32),
                                    max_new_tokens=8)
        fast = router.submit_stream(np.array([4, 5], np.int32),
                                    max_new_tokens=4)
        # consume B to completion while A sits unread
        fast_toks = list(fast)
        assert len(fast_toks) == 4
        assert np.array_equal(fast.result(timeout=60),
                              np.asarray(fast_toks, np.int32))
        # A lost nothing while we ignored it
        slow_toks = list(slow)
        assert len(slow_toks) == 8
        s = router.stats()
        assert s["served"] == 2 and s["requests_lost"] == 0
    finally:
        _drop(replicas)


# ---------------------------------------------------------------------------
# 5. autoscaler


class _FakePool:
    def __init__(self, n=1):
        self.n = n
        self.actions = []

    def replica_count(self):
        return self.n

    def healthy_count(self):
        return self.n

    def load(self):
        return 0.0

    def scale_up(self):
        self.n += 1
        self.actions.append("up")
        return self.n

    def scale_down(self, timeout=60.0):
        self.n -= 1
        self.actions.append("down")
        return self.n


class _FakeMonitor:
    def __init__(self):
        self.state = "ok"

    def status(self):
        return (self.state, [] if self.state == "ok" else ["latency"])


@pytest.fixture
def _ctrl_env():
    """Pin the restart-free autoscaler knobs for the test, then
    restore."""
    names = ("CTRL_COOLDOWN_SEC", "CTRL_SCALE_UP_OCCUPANCY",
             "CTRL_SCALE_DOWN_OCCUPANCY")
    base.setenv("CTRL_COOLDOWN_SEC", 0)
    yield
    for n in names:
        base.setenv(n, None)


def test_autoscaler_hysteresis_cooldown_and_bounds(_ctrl_env):
    pool = _FakePool(n=1)
    loads = []
    scaler = cp.Autoscaler(pool, min_replicas=1, max_replicas=3,
                           up_ticks=2, down_ticks=2,
                           load_fn=lambda: loads.pop(0))
    # hysteresis: ONE hot tick is not a trend
    loads[:] = [0.9, 0.2, 0.9, 0.9]
    assert scaler.tick()["action"] == "hold"
    assert scaler.tick()["action"] == "hold"   # streak broken
    assert scaler.tick()["action"] == "hold"
    assert scaler.tick()["action"] == "up"     # 2 consecutive
    assert pool.n == 2

    # cooldown: a fresh breach inside the window is blocked
    base.setenv("CTRL_COOLDOWN_SEC", 3600)
    before = cp.ctrl_stats()["blocked_cooldown"]
    loads[:] = [0.9, 0.9]
    scaler.tick()
    assert scaler.tick()["action"] == "hold"
    assert cp.ctrl_stats()["blocked_cooldown"] == before + 1
    base.setenv("CTRL_COOLDOWN_SEC", 0)

    # bounds: at max_replicas the breach is tallied, not actuated
    # (the up-streak persisted across the cooldown block, so this
    # single hot tick reaches the actuation gate again)
    pool.n = 3
    before = cp.ctrl_stats()["blocked_bounds"]
    loads[:] = [0.9]
    assert scaler.tick()["action"] == "hold"
    assert cp.ctrl_stats()["blocked_bounds"] == before + 1
    assert pool.n == 3

    # scale down on sustained idle, but never below min_replicas
    loads[:] = [0.1, 0.1, 0.1, 0.1, 0.1, 0.1]
    acts = [scaler.tick()["action"] for _ in range(4)]
    assert acts.count("down") == 2 and pool.n == 1
    before = cp.ctrl_stats()["blocked_bounds"]
    assert scaler.tick()["action"] == "hold"   # streak rebuilding
    assert scaler.tick()["action"] == "hold"   # blocked at the floor
    assert pool.n == 1
    assert cp.ctrl_stats()["blocked_bounds"] == before + 1


def test_autoscaler_slo_pressure_scales_up(_ctrl_env):
    """A firing SLO counts as pressure even when queues look shallow —
    latency degrades before occupancy saturates."""
    pool = _FakePool(n=1)
    mon = _FakeMonitor()
    scaler = cp.Autoscaler(pool, monitor=mon, min_replicas=1,
                           max_replicas=3, up_ticks=2, down_ticks=2,
                           load_fn=lambda: 0.3)
    mon.state = "degraded"
    assert scaler.tick()["action"] == "hold"
    d = scaler.tick()
    assert d["action"] == "up" and "slo" in d["reason"]
    assert pool.n == 2


# ---------------------------------------------------------------------------
# 6. spawn failure classification


def test_spawn_failure_injected_and_classified(tmp_path):
    proc = cp.ReplicaProcess(["/definitely/not/a/binary"],
                             str(tmp_path), "7")
    plan = faults.FaultPlan([{"site": "serve.replica.spawn",
                              "action": "raise"}])
    with faults.armed(plan):
        with pytest.raises(mx.MXNetError) as ei:
            proc.spawn()
    assert classify(ei.value) == "transient"
    assert plan.fired()[0]["ctx"]["replica"] == "7"

    # a real exec failure is a ReplicaSpawnError, also retryable
    with pytest.raises(cp.ReplicaSpawnError) as ei:
        proc.spawn()
    assert classify(ei.value) == "transient"
    assert "spawn failed" in str(ei.value)


# ---------------------------------------------------------------------------
# 7. discovery leases


def test_discovery_rejects_stale_leases(tmp_path):
    d = str(tmp_path)
    live = LeaseDir(d, prefix="replica", lease_sec=5.0)
    live.publish("0", {"host": "h", "port": 1, "pid": 11,
                       "kind": "decode"})
    live.publish("1", {"host": "h", "port": 2, "pid": 22,
                       "kind": "decode"})
    # replica 1 was SIGKILLed long ago: its marker stopped refreshing
    old = time.time() - 3600
    os.utime(live.path_for("1"), (old, old))
    before = cp.ctrl_stats()["stale_leases_rejected"]
    found = cp.discover_replicas(d, lease_sec=5.0)
    assert set(found) == {"0"}
    assert found["0"]["port"] == 1
    assert cp.ctrl_stats()["stale_leases_rejected"] == before + 1
    # a retired lease disappears entirely
    live.retire("0")
    assert cp.discover_replicas(d, lease_sec=5.0) == {}


# ---------------------------------------------------------------------------
# 8. zero-loss audit across a kill


def test_requests_lost_zero_across_connection_kill(decode_pair):
    """A burst with a connection kill in the middle: every request is
    accounted for (served or failed), the audit identity holds at
    exactly zero, and survivors' results stay bit-identical."""
    srv0, _ = decode_pair[0]
    replicas = _remotes(decode_pair)
    router = _router(replicas)
    try:
        rng = np.random.RandomState(11)
        prompts = [rng.randint(0, VOCAB, size=int(rng.randint(2, 7)))
                   .astype(np.int32) for _ in range(6)]
        refs = [[int(t) for t in srv0.generate(p, max_new_tokens=4,
                                               timeout=60)]
                for p in prompts]
        futs = [router.submit(p, max_new_tokens=4) for p in prompts[:3]]
        plan = faults.FaultPlan([{"site": "serve.rpc.send",
                                  "action": "raise",
                                  "match": {"replica": 0}}])
        with faults.armed(plan):
            try:
                replicas[0].ping()   # cut replica 0's wire mid-burst
            except mx.MXNetError:
                pass
        futs += [router.submit(p, max_new_tokens=4)
                 for p in prompts[3:]]
        outs = [f.result(timeout=120) for f in futs]
        for out, ref in zip(outs, refs):
            assert [int(t) for t in out] == ref
        s = router.stats()
        assert s["served"] == 6
        assert s["requests_lost"] == 0
        # the books balance by construction, not by luck:
        assert s["submitted"] == 6
        assert s["failed"] == 0
    finally:
        _drop(replicas)


def test_wire_cut_under_a_full_burst_leaves_the_shared_server_up(
        decode_pair):
    """Eight requests all in flight (the decode loops stalled) when
    replica 0's wire is cut: four fail on it at once, more than the
    default `evict_after`.  Every one is still served by re-dispatch,
    none is lost, nothing is evicted, and the shared server takes the
    next request: what `_router` is for."""
    srv0, _ = decode_pair[0]
    replicas = _remotes(decode_pair)
    router = _router(replicas)
    try:
        rng = np.random.RandomState(12)
        prompts = [rng.randint(0, VOCAB, size=int(rng.randint(2, 7)))
                   .astype(np.int32) for _ in range(8)]
        stall = faults.FaultPlan([{"site": "serve.decode",
                                   "action": "stall", "delay_s": 0.2,
                                   "times": None}])
        with faults.armed(stall):
            futs = [router.submit(p, max_new_tokens=4) for p in prompts]
            assert len(replicas[0]._pending) >= 3
            plan = faults.FaultPlan([{"site": "serve.rpc.send",
                                      "action": "raise",
                                      "match": {"replica": 0}}])
            with faults.armed(plan):
                with pytest.raises(mx.MXNetError):
                    replicas[0].ping()
        assert all(len(f.result(timeout=120)) == 4 for f in futs)
        s = router.stats()
        assert s["served"] == 8 and s["requests_lost"] == 0
        assert s["retries"] >= 3 and s["evictions"] == 0
        assert len(srv0.generate(prompts[0], max_new_tokens=2,
                                 timeout=60)) == 2
    finally:
        _drop(replicas)


# ---------------------------------------------------------------------------
# decode sinks (the multiplexing hook the endpoint rides)


def test_decode_handle_sink_replays_history(decode_pair):
    """add_sink() attached LATE still sees every token exactly once,
    then exactly one terminal — the endpoint can attach whenever the
    submit frame arrives."""
    srv, _ = decode_pair[0]
    handle = srv.submit(np.array([1, 2, 3], np.int32), max_new_tokens=5)
    expect = [int(t) for t in handle.result(timeout=60)]
    seen = []
    done = threading.Event()
    handle.add_sink(lambda item: (seen.append(item),
                                  done.set()
                                  if item is cp.rpc.STREAM_DONE
                                  or isinstance(item, BaseException)
                                  else None))
    assert done.wait(30)
    assert seen[:-1] == expect
    assert seen[-1] is cp.rpc.STREAM_DONE
