"""Host milliseconds a step spends inside the call of the compiled step
(argument flattening and checks, the enqueue): the program's `dp.step.enqueue` span, read from its step log
(harness/step_log.py) over the untraced window's steps."""
from harness import step_log

FIELD = 5


def read(run):
    return step_log.mean_ms(run, FIELD)
