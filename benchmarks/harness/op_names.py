"""Device time of a step by the scopes its program names.

Every instruction of a compiled step carries the `jax.named_scope`s it
was traced under in `metadata={op_name="..."}`, and a device event's
name starts with its instruction's name (`harness.trace.short_name`).
`seconds_a_step(run, wanted)` sums, inside the traced window, the
device time of the events whose instruction's op_name `wanted` accepts,
averaged over the devices, a step.  Instructions the compiler added
with no op_name of their own are not counted, nor are the events of
loops and branches themselves (their bodies' events are).  Nothing to read (no
trace, no compiled text, no step in the window) gives None.
"""
from __future__ import annotations

import re

from harness import scopes, trace

# The TPU compiler lowers `jax.lax.ragged_dot` to Mosaic calls of its own
# (`%ragged-dot-none.N`, and `%ragged-dot-metadata.N` for the groups'
# tiling) and names them `<enclosing jit>/ragged-dot-...`: the
# `named_scope`s the call was traced under are LOST, so a reader of the
# expert layer's time asks for these beside the `/moe/` scope
GROUPED_PRODUCT = "/ragged-dot-"

# a loop's or a branch's own event spans the events of its body, which
# the trace holds too: counting both would count the body twice
CONTAINER = re.compile(r"^(while|conditional|cond|call)[.\d]*( |$)")


def instruction_op_names(program_text):
    """{instruction name: op_name} of the compiled text."""
    out = {}
    for line in program_text.splitlines():
        found = scopes.DEFINITION.match(line)
        if found:
            op_name = scopes.OP_NAME.search(found.group(2))
            if op_name:
                out[found.group(1)] = op_name.group(1)
    return out


def of_run(run):
    """The map of this run's step program, parsed once a run."""
    if not hasattr(run, "instruction_op_names"):
        run.instruction_op_names = None
        if run.trace is not None and run.program_text is not None:
            run.instruction_op_names = instruction_op_names(
                run.program_text())
    return run.instruction_op_names


def seconds_a_step(run, wanted):
    names = of_run(run)
    if not names:
        return None
    steps = len(trace.step_starts(run.trace, run.traffic["step_program"]))
    start, end = trace.window(run.trace)
    total = hits = 0
    for dev in run.trace["devices"].values():
        for name, s, d in dev["ops"]:
            inside = min(s + d, end) - max(s, start)
            if inside > 0 and not CONTAINER.match(name) and wanted(
                    names.get(name.split(" ", 1)[0], "")):
                total += inside
                hits += 1
    if not steps or not hits:
        return None
    return total / len(run.trace["devices"]) / steps / 1e9
