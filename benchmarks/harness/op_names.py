"""Device time of a step by the scopes its program names.

Every instruction of a compiled step carries the `jax.named_scope`s it
was traced under in `metadata={op_name="..."}`, and a device event's
name starts with its instruction's name (`harness.trace.short_name`).
`seconds_a_step(run, wanted)` sums, inside the traced window, the
device time of the events whose instruction's op_name `wanted` accepts,
averaged over the devices, a step.  Instructions the compiler added
with no op_name of their own are not counted, nor are the events of
loops, branches and calls themselves (their bodies' events are:
`harness.trace.operations` leaves every container out, by the
benchmark's one match, `harness.trace.CONTAINER`).  A reader by scope
calls this function and sums no event on its own.  Nothing to read (no
trace, no compiled text, no step in the window) gives None.
"""
from __future__ import annotations

from harness import scopes, trace

# The TPU compiler lowers `jax.lax.ragged_dot` to Mosaic calls of its own
# (`%ragged-dot-none.N`, and `%ragged-dot-metadata.N` for the groups'
# tiling) and names them `<enclosing jit>/ragged-dot-...`: the
# `named_scope`s the call was traced under are LOST, so a reader of the
# expert layer's time asks for these beside the `/moe/` scope
GROUPED_PRODUCT = "/ragged-dot-"


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


def seconds_a_step(run, wanted, unless=None):
    """`unless`, a compiled pattern, leaves out the events whose own
    NAME it finds (a Mosaic call's name holds its target and its first
    operand's shape, which no op_name does)."""
    names = of_run(run)
    if not names:
        return None
    steps = len(trace.step_starts(run.trace, run.traffic["step_program"]))
    total = hits = 0
    for name, inside in trace.operations(run.trace):
        if wanted(names.get(name.split(" ", 1)[0], "")) and not (
                unless and unless.search(name)):
            total += inside
            hits += 1
    if not steps or not hits:
        return None
    return total / len(run.trace["devices"]) / steps / 1e9
