"""Traffic kind `train_steps`: a closed loop of training steps through
the trainer's own `step(x, y)`, a fresh host batch every step from a
pool made from the seed, the loss read every `loss_every` steps and at
the close.

Parameters (the cell's file under workloads/): `batch` and whatever
else the configuration's `make_batch` reads, `pool`, `loss_every`,
`check_steps` (how many of the first steps the reference follows),
`rate_metric` (the name under which units per second are reported),
`step_program` (what the step's device program is called in a trace),
`traced_steps`, `limits`; and, where the WEIGHTS decide how much work a
step is (a router that sends its rows by them), `weights_seed`: the
weights of program and reference are then made from it whatever
`--seed` says, and `--seed` draws the batches alone, so every seed
gives the same work on other inputs.
"""
from __future__ import annotations

import contextlib
import gc
import time

import jax
import jax.monitoring
import jax.profiler
import numpy as np

from harness import compare, gluon_program, weights
from harness import trace as trace_mod

COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


class CompileCounter:
    """Counts the programs compiled, or loaded from the persistent
    cache, while `counting` is on."""

    def __init__(self):
        self.count = 0
        self.counting = False
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, _seconds, **_kw):
        if self.counting and event in COMPILE_EVENTS:
            self.count += 1


def _seed32(seed):
    return int(seed) % (2 ** 32)


def make_pool(job):
    rng = np.random.RandomState(_seed32(job.seed))
    return [job.config_mod.make_batch(rng, job.config, job.traffic)
            for _ in range(job.traffic["pool"])]


def seeded_weights(job):
    return weights.make(_seed32(job.traffic.get("weights_seed", job.seed)),
                        job.reference_mod.param_specs(job.config))


def check_steps(job, trainer, pool):
    """Drive the step object through its first steps by the window's own
    call and feed, and read what the comparison needs."""
    import mxnet_tpu as mx

    n = job.traffic["check_steps"]
    optimizer = job.config["assumed"]["optimizer"]
    parts = job.reference_mod.leaf_parts(job.config)
    trainer.build(pool[0][0])
    # the dropout stream starts here: step t draws from
    # fold_in(PRNGKey(seed), t), as the reference does
    mx.random.seed(_seed32(job.seed))
    losses, grad_norms = [], None
    for t in range(n):
        x, y = pool[t % len(pool)]
        losses.append(float(trainer.step(x, y).asnumpy()))
        if t == 0:
            grad_norms = gluon_program.first_gradient_norms(
                trainer, optimizer, parts)
    change = gluon_program.change_norms(trainer, seeded_weights(job), parts)
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change}


def window(job, trainer, pool, seconds, first_batch,
           annotate=contextlib.nullcontext, max_steps=None):
    """The timed loop.  Returns (steps, window seconds, host seconds
    inside the step calls, last loss): every step counted has finished
    on the device when the clock is read."""
    loss_every = job.traffic["loss_every"]
    steps = 0
    dispatch = 0.0
    t_open = time.perf_counter()
    while True:
        with annotate("host_batch"):
            x, y = pool[(first_batch + steps) % len(pool)]
        t0 = time.perf_counter()
        with annotate("step_call"):
            loss = trainer.step(x, y)
        dispatch += time.perf_counter() - t0
        steps += 1
        if steps % loss_every == 0:
            with annotate("loss_read"):
                loss.wait_to_read()
        if time.perf_counter() - t_open >= seconds or steps == max_steps:
            break
    with annotate("loss_read"):
        loss.wait_to_read()
    return steps, time.perf_counter() - t_open, dispatch, float(loss.asnumpy())


def measure(job):
    """Set-up, then the window; with `job.trace`, a traced stretch of
    steady steps first.  Returns the run's record."""
    counter = CompileCounter()
    trainer = job.config_mod.build(job.config, job.traffic,
                                   seeded_weights(job))
    pool = make_pool(job)
    record = {"trainer": trainer, "pool": pool,
              "program": check_steps(job, trainer, pool),
              "trainable": gluon_program.trainable_flags(
                  trainer, job.reference_mod.leaf_parts(job.config)),
              "spans": {}, "counters": {}}
    n = job.traffic["check_steps"]
    counter.counting = True
    setup_s = time.perf_counter() - job.t_start
    if job.trace:
        # host spans come from the annotations alone: with the python
        # tracer on, ResNet's traced steps took 196 ms against 93 ms
        # without (50 ms untraced; a lower host tracer level did not
        # help: 198 ms at level 1; my chip runs, PR 26)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(job.trace_dir, profiler_options=options)
        try:
            with jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN):
                traced = window(job, trainer, pool, job.seconds, n,
                                jax.profiler.TraceAnnotation,
                                max_steps=job.traffic["traced_steps"])
        finally:
            jax.profiler.stop_trace()
        n += traced[0]
        record["trace_path"] = trace_mod.find_xplane(job.trace_dir)
    steps, seconds, dispatch, last_loss = window(
        job, trainer, pool, job.seconds, n)
    counter.counting = False
    units = job.config_mod.units_per_step(job.config, job.traffic)
    record["end_to_end"] = {
        job.traffic["rate_metric"]: units * steps / seconds,
        "step_ms": seconds / steps * 1000.0,
        "setup_s": setup_s,
    }
    record["attempted"] = steps
    record["failed"] = 0 if np.isfinite(last_loss) else steps
    record["spans"]["step_call"] = {"seconds": dispatch, "count": steps}
    record["spans"]["window"] = {"seconds": seconds, "count": steps}
    record["counters"]["compiles_in_window"] = counter.count
    record["program_text"] = lambda: gluon_program.step_program_text(
        trainer, *pool[0])
    return record


def reference_inputs(job, pool):
    """(params0, batches) the reference follows: weights from the seed
    and the batches of the program's first steps."""
    n = job.traffic["check_steps"]
    return (seeded_weights(job),
            [job.config_mod.reference_batch(*pool[t % len(pool)])
             for t in range(n)])


def read_seed(job, control=False, faults=False, raw=False):
    """The numbers compared for one seed with no window: the program
    against the reference and, when asked, the control (the reference
    one precision below the configuration's) and the half-batch fault
    (the reference on the first half of each batch) against it too."""
    trainer = job.config_mod.build(job.config, job.traffic,
                                   seeded_weights(job))
    pool = make_pool(job)[:job.traffic["check_steps"]]
    program = check_steps(job, trainer, pool)
    trainable = gluon_program.trainable_flags(
        trainer, job.reference_mod.leaf_parts(job.config))
    del trainer
    gc.collect()
    jax.clear_caches()
    params0, batches = reference_inputs(job, pool)
    follow = job.reference_mod.follow
    reference = follow(job.config, params0, batches, _seed32(job.seed))
    sides = {"program": program}
    if control:
        sides["control"] = follow(
            job.config, params0, batches, _seed32(job.seed),
            precision=job.config["assumed"]["control_precision"])
    if faults:
        sides["half_batch"] = follow(
            job.config, params0, batches, _seed32(job.seed),
            rows=job.traffic["batch"] // 2)
    out = {"seed": job.seed}
    for name, side in sides.items():
        out[name] = compare.readings(side, reference, trainable)
    if raw:
        sides["reference"] = reference
        out["raw"] = {name: {k: np.asarray(v).tolist()
                             for k, v in side.items()}
                      for name, side in sides.items()}
        out["raw"]["trainable"] = list(trainable)
    return out


def verify(job, record):
    """Free the program's state, follow the same steps with the plain
    reference, and compare.  Returns (compared, correct)."""
    trainable = record["trainable"]
    pool = record["pool"]
    for key in ("trainer", "program_text"):
        record.pop(key, None)
    gc.collect()
    jax.clear_caches()
    params0, batches = reference_inputs(job, pool)
    t0 = time.perf_counter()
    reference = job.reference_mod.follow(
        job.config, params0, batches, _seed32(job.seed))
    record["reference_s"] = time.perf_counter() - t0
    values = compare.readings(record["program"], reference, trainable)
    record["readings"] = values
    return compare.judge(values, job.traffic["limits"])
