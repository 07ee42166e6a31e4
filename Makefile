# Native components (ref: the reference's C++ core; here the IO/runtime
# tier — the compute tier is XLA/Pallas).
CXX ?= g++
CXXFLAGS ?= -O3 -std=c++17 -fPIC -Wall -pthread
LDFLAGS ?= -shared -ljpeg

LIB := lib/libmxtpu_io.so
ENGINE_LIB := lib/libmxtpu_engine.so
STORAGE_LIB := lib/libmxtpu_storage.so
CAPI_LIB := lib/libmxtpu_capi.so

PY_INCLUDES := $(shell python3-config --includes)
PY_LDFLAGS := $(shell python3-config --ldflags --embed 2>/dev/null || python3-config --ldflags)

# the C ABI embeds CPython: only build it where dev headers exist, so a
# bare `make` still succeeds on hosts without python3-dev
HAS_PYCONFIG := $(shell command -v python3-config 2>/dev/null)
ALL_LIBS := $(LIB) $(ENGINE_LIB) $(STORAGE_LIB)
ifneq ($(HAS_PYCONFIG),)
ALL_LIBS += $(CAPI_LIB)
endif

all: $(ALL_LIBS)

$(CAPI_LIB): src/c_api.cc
	@mkdir -p lib
	$(CXX) $(CXXFLAGS) $(PY_INCLUDES) $< -o $@ -shared $(PY_LDFLAGS)

$(STORAGE_LIB): src/storage.cc
	@mkdir -p lib
	$(CXX) $(CXXFLAGS) $< -o $@ -shared

$(LIB): src/recordio.cc
	@mkdir -p lib
	$(CXX) $(CXXFLAGS) $< -o $@ $(LDFLAGS)

$(ENGINE_LIB): src/engine.cc
	@mkdir -p lib
	$(CXX) $(CXXFLAGS) $< -o $@ -shared -pthread

clean:
	rm -rf lib

test: all
	python -m pytest tests/ -x -q

# serving-tier gate: ModelServer on a tiny model, 100 requests,
# stats invariants (served == submitted - rejected, closed compile
# surface) — see tools/serve_smoke.py / docs/serving.md
serve-smoke:
	env PYTHONPATH=. python tools/serve_smoke.py

# fault-tolerant-serving gate: a 3-replica Router pool survives an
# injected replica kill + health-probe stall mid-burst — every admitted
# request resolves or fails classified, the pool heals back to 3 with
# zero in-traffic compiles on survivors, and a rolling reload under
# load drops zero requests — see tools/router_smoke.py /
# docs/serving.md
router-smoke:
	env PYTHONPATH=. python tools/router_smoke.py

# continuous-batching gate: a staggered 50-request burst through a
# 4-slot DecodeServer arena — zero post-warmup compiles, exact
# dispatch-per-token accounting, every admitted request resolves, and
# the disarmed-hook overhead budget — see tools/decode_smoke.py /
# docs/serving.md
decode-smoke:
	env PYTHONPATH=. python tools/decode_smoke.py

# paged + speculative decoding gate: a heavy-tailed 50-request burst
# through a paged KV arena sized to HALF the contiguous cache HBM,
# with a draft model proposing speculative blocks — every request
# resolves, zero post-warmup compiles, exact dispatch accounting
# (verify + draft + admissions), acceptance rate > 0, and the page
# allocator ledger balances — see tools/paged_decode_smoke.py /
# docs/serving.md
paged-smoke:
	env PYTHONPATH=. python tools/paged_decode_smoke.py

# compiled-INT8 serving gate: calibrate -> quantize -> serve a request
# burst through ModelServer + a decode burst through DecodeServer —
# zero post-warmup compiles, exact dispatch accounting (one executable
# per batch / per token step), >= 99% argmax agreement with fp32,
# compiled==eager bit parity — see tools/int8_smoke.py /
# docs/quantization.md
int8-smoke:
	env PYTHONPATH=. python tools/int8_smoke.py

# step-fusion gate: 50 fused Trainer.step()s under a decaying LR
# schedule with zero post-warmup compiles + fused/sequential bit
# parity — see tools/step_fusion_smoke.py / docs/performance.md
step-fusion-smoke:
	env PYTHONPATH=. python tools/step_fusion_smoke.py

# whole-step gate: 50 compiled whole steps at ONE device dispatch each
# (global dispatch counter), zero post-warmup compiles under LR decay,
# and 5-step whole-step/fused/sequential bit parity — see
# tools/whole_step_smoke.py / docs/performance.md
whole-step-smoke:
	env PYTHONPATH=. python tools/whole_step_smoke.py

# ZeRO-1 gate: 50 sharded whole steps on the virtual 8-device mesh at
# ONE counted dispatch each, zero post-warmup compiles under LR decay,
# 5-step sharded/unsharded bit parity, and per-replica optimizer-state
# bytes < unsharded/2 — see tools/zero_shard_smoke.py /
# docs/performance.md
zero-smoke:
	env PYTHONPATH=. python tools/zero_shard_smoke.py

# multi-axis spmd mesh gate: 30 whole steps on a (dp=4,mp=2) mesh at
# ONE dispatch / 0 post-warmup compiles each under LR decay, optimizer
# state measured < 1/4 full bytes on any device, allclose parity with
# the single-device whole step, and a (dp=4,mp=2) -> (dp=2,mp=2)
# elastic restore adopting params + state bit-exactly — see
# tools/spmd_smoke.py / docs/parallelism.md
spmd-smoke:
	env PYTHONPATH=. python tools/spmd_smoke.py

# input-pipeline gate: prefetch overlap engaged, zero post-warmup
# compiles over mixed lengths, bit-identical mid-epoch resume — see
# tools/pipeline_smoke.py / docs/data.md
pipeline-smoke:
	env PYTHONPATH=. python tools/pipeline_smoke.py

# resilience gate: a supervised run survives one injected SIGTERM and
# one injected transient collective failure bit-identically, with the
# recovery visible in the profiler and zero disarmed fault-point
# overhead, and the runtime lock-order checker observes zero
# inversions — see tools/chaos_smoke.py / docs/resilience.md
chaos-smoke:
	env PYTHONPATH=. python tools/chaos_smoke.py

# elastic world-size gate: kill k of N virtual ranks mid-run — the
# supervisor resizes to N-k (the resize itself surviving an injected
# transient failure), the resharding restore repartitions the latest
# checkpoint, and the resumed run is bit-identical to a fresh job
# started at N-k, at exactly one resize recompile then 1 dispatch /
# 0 compiles per step — see tools/elastic_smoke.py /
# docs/checkpointing.md "Elastic restore"
elastic-smoke:
	env PYTHONPATH=. python tools/elastic_smoke.py

# observability gate: one traced train+serve run emits spans from all
# five subsystems into valid Chrome trace-event JSON, an injected
# watchdog fire leaves a loadable flight-recorder dump, /metrics
# serves Prometheus text agreeing with profiler.dumps(), and the
# disarmed telemetry hooks cost ~nothing — see tools/trace_smoke.py /
# docs/observability.md
trace-smoke:
	env PYTHONPATH=. python tools/trace_smoke.py

# health-monitor gate: a supervised pipeline-fed run under an armed
# HealthMonitor — an injected straggler stall is named (rank + phase)
# within K ticks, a deliberately input-starved phase fires the SLO
# rule and flips /healthz degraded->ok, goodput debits injected
# restart time, MFU is reported for the whole-step path,
# mxtpu_health_* scrapes agree with dumps, zero post-warmup compiles,
# and the disarmed hook costs ~nothing — see tools/health_smoke.py /
# docs/observability.md "Health monitor"
health-smoke:
	env PYTHONPATH=. python tools/health_smoke.py

# autotuner gate: from a deliberately bad config (1 MB buckets,
# aggregate_num=1, no prefetch, zero linger, one giant serve bucket)
# the closed loop must escape by a gated margin on a real
# training+serving rehearsal, beat-or-tie the hand-tuned defaults,
# leave a replayable evidence trail on disk, and settle on a config
# whose serving surface is closed (zero post-warmup compiles) — see
# tools/tune_smoke.py / docs/tuning.md
tune-smoke:
	env PYTHONPATH=. python tools/tune_smoke.py

# serving control-plane CI gate: three replica worker PROCESSES behind
# the socket RPC router — load triples -> warm scale-up with zero
# in-traffic compiles, idle drains back down, a SIGKILLed replica
# process fails over mid-stream within the SLO with requests_lost==0,
# and the episode shows in the mxtpu_ctrl_* gauges — see
# tools/ctrl_smoke.py / docs/serving.md
ctrl-smoke:
	env PYTHONPATH=. python tools/ctrl_smoke.py

# static-analysis gate: the mxtpu-analyze pass families (lock-order
# races, trace-safety, determinism, repo invariants) must run clean
# modulo the justified baseline, within the ~30s latency budget — see
# tools/mxtpu_analyze.py / docs/static-analysis.md
analyze:
	env JAX_PLATFORMS=cpu PYTHONPATH=. python tools/mxtpu_analyze.py

# the ROADMAP tier-1 gate, verbatim ($$ = make-escaped shell $)
verify: SHELL := /bin/bash
verify: analyze serve-smoke router-smoke decode-smoke paged-smoke int8-smoke step-fusion-smoke whole-step-smoke zero-smoke spmd-smoke pipeline-smoke chaos-smoke elastic-smoke trace-smoke health-smoke tune-smoke ctrl-smoke
	set -o pipefail; rm -f /tmp/_t1.log; timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=$${PIPESTATUS[0]}; echo DOTS_PASSED=$$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$$' /tmp/_t1.log | tr -cd . | wc -c); exit $$rc

.PHONY: all clean test verify analyze serve-smoke router-smoke decode-smoke paged-smoke int8-smoke step-fusion-smoke whole-step-smoke zero-smoke spmd-smoke pipeline-smoke chaos-smoke elastic-smoke trace-smoke health-smoke tune-smoke ctrl-smoke
