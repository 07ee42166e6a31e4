"""Device time a step of the events under a scope, with EVERY loop's,
branch's and call's own event left out.

`harness.op_names.seconds_a_step` does the arithmetic, with a
`CONTAINER` that matches `while.N` / `cond.N` and misses the
`while.N.clone.M` XLA gives an instruction it clones as it inlines a
shared function (PERF.md section 7); a container's own event spans its
body's events, which the trace holds too.  Here the containers are
taken out of the trace by the wider match before that arithmetic runs.
The readers a later PR adds use this one; the accepted readers keep
theirs until a `benchmark` PR repairs it.
"""
from __future__ import annotations

import copy
import re

from harness import op_names

CONTAINER = re.compile(r"^(while|conditional|cond|call)[.\w-]*( |$)")


def seconds_a_step(run, wanted):
    """Summed device time a step, averaged over the devices, of the
    events inside the traced window whose instruction's op_name
    `wanted` accepts, containers left out; None where there is nothing
    to read (no trace, no compiled text, no step, no such event)."""
    if not op_names.of_run(run):        # parsed once, kept on `run`
        return None
    pruned = copy.copy(run)
    pruned.trace = dict(run.trace, devices={
        plane: dict(dev, ops=[e for e in dev["ops"]
                              if not CONTAINER.match(e[0])])
        for plane, dev in run.trace["devices"].items()})
    return op_names.seconds_a_step(pruned, wanted)
