"""Set-up as the program books it: the trainer's `build_record`, the
compile account of each `step()` call in `step_log()` (fields 7-10:
trace_ns, lower_ns, compile_ns, cache_loads) and `profiler.
compile_log()`, one record a jax compile event (end_ns, kind, dur_ns,
the op scopes open on its thread joined by `/`).

"The trainer" is the newest live `DataParallelTrainer`.  Its set-up
spans are `dp.build` and the `dp.step` calls of its check steps
(`t <= check_steps`); set-up ends where its step `check_steps + 1`
begins, which cuts out whatever the window and the readers compile
after it (`program_text()` compiles the step again).  The five readers
are disjoint: the build less what jax made inside it, then trace, lower
and compile inside those spans (each a union, booked by the program),
then the union of every other event that ended before set-up's end.
So their sum is at most `setup_s`; the rest is imports, the batch pool,
the check steps' device time and Python.  A program without the
account (the parent of the PR that brought it) gives no number, nor
does a compile log that is full: it keeps the newest
`profiler.COMPILE_LOG_KEPT` events, and set-up's first ones may be
gone."""
from __future__ import annotations

FIELDS = {"trace": 7, "lower": 8, "compile": 9}
TRAINER_SPANS = {"dp.build", "dp.step"}


def setup(run):
    """(build record, the set-up steps' records, set-up's end in ns),
    or None."""
    try:
        from mxnet_tpu.parallel import data_parallel
        build = data_parallel.live_trainers()[-1].build_record
        records = data_parallel.step_log()
    except (ImportError, AttributeError, IndexError):
        return None
    if build is None:
        return None
    n = run.traffic["check_steps"]
    mine = {r[1]: r for r in records
            if r[0] == build.serial and len(r) > FIELDS["compile"]}
    if any(t not in mine for t in range(1, n + 2)):
        return None
    return build, [mine[t] for t in range(1, n + 1)], mine[n + 1][2]


def booked_s(run, kind):
    """Seconds of `kind` ("trace", "lower" or "compile") jax spent
    inside the trainer's set-up spans."""
    got = setup(run)
    if got is None:
        return None
    build, steps, _end = got
    ns = getattr(build, kind + "_ns") + sum(r[FIELDS[kind]] for r in steps)
    return ns / 1e9


def build_s(run):
    """`dp.build`'s seconds less what jax made inside it."""
    got = setup(run)
    if got is None:
        return None
    build = got[0]
    return (build.total_ns - build.trace_ns - build.lower_ns
            - build.compile_ns) / 1e9


def union_ns(intervals):
    """Nanoseconds covered by [(start, end)], overlaps counted once."""
    total, reach = 0, None
    for start, end in sorted(intervals):
        if reach is None or start > reach:
            total += end - start
            reach = end
        elif end > reach:
            total += end - reach
            reach = end
    return total


def other_programs_s(run):
    """Seconds covered by the trace, lower and compile events that
    ended before set-up's end outside the trainer's set-up spans (a
    cache load lies inside its compile event)."""
    got = setup(run)
    if got is None:
        return None
    end = got[2]
    try:
        from mxnet_tpu import profiler
        log = profiler.compile_log()
        kept = profiler.COMPILE_LOG_KEPT
    except (ImportError, AttributeError):
        return None
    if len(log) >= kept:
        return None
    return union_ns(
        (stop - dur, stop) for stop, kind, dur, path in log
        if kind in FIELDS and stop <= end
        and not TRAINER_SPANS & set(path.split("/"))) / 1e9
