"""The look behind a cell's worst leaf, on the chip at the cell's own
size: for each seed the program's check steps and the reference's, with
the rows every held expert got and every expert tensor's gradient norm
read after EACH step on both sides, and the worst leaves by change
named:

    python3 benchmarks/look.py --workload laguna_xs2.seq8k --seeds 1,2 [--learning-rate 1e-4]

One JSON line a seed.  For a configuration whose reference names its
leaves (`leaf_names`) and whose `follow` takes `watch` (the decoder).
Not part of a benchmark run; what PERF.md section 2 says of
`change_norm_gap` was read with it.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


def program_side(job):
    """`check_steps` with the trainer's `step` wrapped: after every step
    the routing log (`aux_params()`) and the gradient norm of every
    stacked expert tensor, from the first moments before and after
    (m_t = b1 m_(t-1) + (1 - b1) g_t; copies of the experts' moments
    are what fits beside the step)."""
    import jax.numpy as jnp
    import numpy as np

    from harness import gluon_program

    tm = job.traffic_mod
    trainer = job.config_mod.build(job.config, job.traffic,
                                   tm.seeded_weights(job))
    pool = tm.make_pool(job)[:job.traffic["check_steps"]]
    parts = tuple(job.reference_mod.leaf_parts(job.config))
    beta1 = job.config["assumed"]["optimizer"]["beta1"]
    stacked = [i for i, k in enumerate(parts)
               if k == job.config["num_experts"] > 1]
    starts = np.cumsum((0,) + parts)
    logs, grads, before = [], [], []
    step = trainer.step

    def watched(x, y):
        out = step(x, y)
        out.wait_to_read()
        aux = list(trainer.aux_params().values())
        logs.append(aux[0] if aux else None)
        now = [trainer._states[i][0] for i in stacked]
        old = before or [jnp.zeros_like(m) for m in now]
        norms = np.asarray(gluon_program._norms(
            [(m - beta1 * b) / (1 - beta1) for m, b in zip(now, old)],
            tuple(parts[i] for i in stacked)))
        row = np.full(int(starts[-1]), np.nan)
        for i, chunk in zip(stacked, np.split(norms, len(stacked))):
            row[starts[i]:starts[i + 1]] = chunk
        grads.append(row)
        # the step donates its state: copies for the next reading
        before[:] = [jnp.array(m, copy=True) for m in now]
        return out

    trainer.step = watched
    program = tm.check_steps(job, trainer, pool)
    return (program, gluon_program.trainable_flags(trainer, parts), pool,
            logs, grads)


def look_seed(job, worst=6):
    """One seed's record: the readings, then the `worst` leaves by
    change with their rows and gradient norms a step on both sides."""
    import jax
    import numpy as np

    from harness import compare

    ref, config = job.reference_mod, job.config
    program, trainable, pool, p_logs, p_grads = program_side(job)
    gc.collect()
    jax.clear_caches()
    r_logs, r_grads = [], []
    params0, batches = job.traffic_mod.reference_inputs(job, pool)
    reference = ref.follow(
        config, params0, batches, job.seed % 2 ** 32,
        watch=lambda t, routing, norms: (r_logs.append(routing),
                                         r_grads.append(norms)))
    record = {"seed": job.seed,
              "learning_rate": config["assumed"]["optimizer"][
                  "learning_rate"],
              "readings": compare.readings(program, reference, trainable)}
    kept = np.asarray(trainable)
    first = np.asarray(reference["grad_norms"], np.float64)
    moved = ~kept | (first >= compare.NEGLIGIBLE_GRADIENT
                     * np.median(first[kept]))
    gaps = compare.leaf_gaps(program["change_norms"],
                             reference["change_norms"], moved)
    record["median_leaf"] = {
        "first_gradient": float(np.median(first[kept])),
        "change": float(np.median(
            np.asarray(reference["change_norms"])[moved]))}
    names = ref.leaf_names(config)
    sparse = [i for i, (_, _, s) in enumerate(ref._layers(config)) if s]
    record["worst"] = []
    for i in (int(i) for i in np.argsort(-gaps)[:worst]):
        entry = {"leaf": i, "name": names[i], "change_gap": float(gaps[i]),
                 "change": [float(program["change_norms"][i]),
                            float(reference["change_norms"][i])],
                 "gradient_program": [float(g[i]) for g in p_grads],
                 "gradient_reference": [float(g[i]) for g in r_grads]}
        if "expert_" in names[i]:
            layer = sparse.index(int(names[i].split(".")[0][5:]))
            expert = int(names[i].split("[")[1][:-1])
            entry["rows_program"] = [int(x[layer][expert]) for x in p_logs]
            entry["rows_reference"] = [int(x[layer][expert])
                                       for x in r_logs]
        record["worst"].append(entry)
    for side, logs in (("program", p_logs), ("reference", r_logs)):
        record["rows_" + side] = [x[:, :-1].astype(int).tolist()
                                  for x in logs]
    return record


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--learning-rate", type=float,
                        help="another rate than the configuration's")
    args = parser.parse_args(argv)
    sys.path.insert(0, BENCH_DIR)
    import run

    run.setup_environment()
    from harness import device
    from harness.manifest import Manifest

    manifest = Manifest(run.ROOT)
    try:
        dev = device.require_chips(manifest.cell(args.workload)["chips"])
    except (KeyError, device.NoChip) as e:
        print(f"benchmarks/look.py: {e}", file=sys.stderr)
        return 3
    for seed in (int(s) for s in args.seeds.split(",")):
        job = run.make_job(manifest, args.workload, seed, 0.0, 0, dev)
        if args.learning_rate:
            job.config["assumed"]["optimizer"][
                "learning_rate"] = args.learning_rate
        print(json.dumps(look_seed(job)), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
