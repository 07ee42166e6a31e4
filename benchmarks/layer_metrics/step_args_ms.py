"""Host milliseconds a step spends on the step's PRNG key (an eager
`fold_in`) and its two scalars, three small device programs: the
program's `dp.step.args` span, read from its step log
(harness/step_log.py) over the untraced window's steps.  The `fold_in`
is the first program a step executes, so a wait on the runtime's bound
on programs in flight shows here (PERF.md section 5)."""
from harness import step_log

FIELD = 4


def read(run):
    return step_log.mean_ms(run, FIELD)
