"""Host milliseconds a step spends putting the batch on the device
(`jnp.asarray` + `global_put`): the program's `dp.step.put` span, read
from its step log (harness/step_log.py) over the untraced window's
steps."""
from harness import step_log

FIELD = 3


def read(run):
    return step_log.mean_ms(run, FIELD)
