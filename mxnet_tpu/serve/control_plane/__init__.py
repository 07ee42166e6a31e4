"""Multi-host serving control plane: the process boundary for the
serving stack.

PR 14's :class:`~..router.Router` fronts replicas living in ITS OWN
process; this package promotes it to a control plane fronting replicas
in OTHER processes/hosts — the millions-of-users story — without
changing the router's failure matrix:

- :mod:`.rpc` — the socket wire.  A replica process wraps its started
  ``ModelServer``/``DecodeServer`` in a :class:`~.rpc.ReplicaEndpoint`
  (length-prefixed frames over a threaded stdlib ``socketserver``, the
  ``telemetry.httpd`` idiom; payloads ride the versioned
  ``utils/serialization.py`` container) and registers itself in a
  shared-storage lease directory (``parallel.dist.LeaseDir`` — the
  elastic-rendezvous freshness protocol, not a second one).  A
  :class:`~.rpc.RemoteReplica` client speaks the same
  ``submit()/pending()/probe_example()/reload_weights()/drain()``
  surface the Router already scores and evicts, so classified retries,
  hedging, quotas, health eviction, and rolling reload apply to
  cross-process replicas unchanged.
- :mod:`.pool` — :class:`~.pool.ReplicaProcess` (spawn + registration
  wait; workers AOT-warm BEFORE registering, so admission is always
  warm) and :class:`~.pool.ControlPlane` (spawn-backed Router factory +
  the ``scale_up()/scale_down()`` actuation surface).
- :mod:`.autoscale` — :class:`~.autoscale.Autoscaler`: a ticker
  consuming HealthMonitor windows + router/decode gauges with
  hysteresis, min/max bounds and a cooldown, actuating through the
  warm-spare admission and drain paths so scaling NEVER serves a cold
  compile in traffic.

Observability: this module's window counters are the profiler's
``ctrl`` section (``mxtpu_ctrl_*`` on /metrics via the section
collector); scaling decisions emit ``serve.ctrl.scale`` instants and
every endpoint request runs under a ``serve.rpc.request`` span
(docs/observability.md).
"""
from __future__ import annotations

import threading

from ... import profiler

# ---------------------------------------------------------------------------
# window-scoped module counters: the profiler's `ctrl` section
# (registered below; exported to /metrics as
# mxtpu_ctrl_* gauges by the section collector)

_sec_lock = threading.Lock()
_sec = {"ticks": 0, "scale_ups": 0, "scale_downs": 0,
        "blocked_cooldown": 0, "blocked_bounds": 0,
        "spawns": 0, "spawn_failures": 0, "retired": 0,
        "rpc_requests": 0, "rpc_streams": 0, "rpc_errors": 0,
        "stale_leases_rejected": 0,
        "replicas": 0, "load": 0.0}


def _sec_bump(replicas=None, load=None, **deltas):
    with _sec_lock:
        for k, n in deltas.items():
            _sec[k] += n
        if replicas is not None:
            # level gauges, not counters: the latest tick's pool size
            # and load signal
            _sec["replicas"] = int(replicas)
        if load is not None:
            _sec["load"] = round(float(load), 4)


def ctrl_stats():
    """Window snapshot of the control-plane counters (RPC traffic,
    spawn/retire churn, autoscaler decisions and the blocked-action
    tallies that explain a pool that is NOT moving)."""
    with _sec_lock:
        return dict(_sec)


def reset_ctrl_stats():
    with _sec_lock:
        for k in _sec:
            _sec[k] = 0.0 if k == "load" else 0


profiler.register_section(
    "ctrl", ctrl_stats, reset_ctrl_stats, profiler.rows_table(
        "Serving Control Plane",
        (("autoscaler ticks", "ticks"),
         ("scale-ups", "scale_ups"),
         ("scale-downs", "scale_downs"),
         ("actions blocked by cooldown", "blocked_cooldown"),
         ("actions blocked by bounds", "blocked_bounds"),
         ("replica processes spawned", "spawns"),
         ("replica spawn failures", "spawn_failures"),
         ("replicas drained and retired", "retired"),
         ("rpc requests served", "rpc_requests"),
         ("rpc streams opened", "rpc_streams"),
         ("rpc errors", "rpc_errors"),
         ("stale leases rejected", "stale_leases_rejected"),
         ("pool size (last tick)", "replicas"),
         ("mean occupancy (last tick)", "load"))))


from .autoscale import Autoscaler                          # noqa: E402
from .pool import (ControlPlane, ReplicaProcess,           # noqa: E402
                   ReplicaSpawnError)
from .rpc import (RPCConnectionError, RemoteReplica,       # noqa: E402
                  ReplicaEndpoint, WIRE_VERSION, discover_replicas,
                  recv_frame, send_frame, serve_replica)

__all__ = [
    "Autoscaler", "ControlPlane", "RPCConnectionError",
    "RemoteReplica", "ReplicaEndpoint", "ReplicaProcess",
    "ReplicaSpawnError", "WIRE_VERSION", "ctrl_stats",
    "discover_replicas", "recv_frame", "reset_ctrl_stats",
    "send_frame", "serve_replica",
]
