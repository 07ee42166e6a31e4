"""Device time of a training step by the phase its program names.

`parallel.DataParallelTrainer` builds its step under
`jax.named_scope("forward")` and `("optimizer")`, and JAX's transforms
name the rest, so every instruction of the compiled program carries its
phase in `metadata={op_name="..."}`:

    jit(step)/jvp(forward)/...             forward
    jit(step)/transpose(jvp(forward))/...  backward
    jit(step)/optimizer/...                optimizer

A device event's name starts with its HLO instruction's name
(`harness.trace.short_name` keeps it), and the compiled text maps that
instruction to its `op_name`.  A fusion goes where its own
instruction's metadata puts it.  What the compiler added with no
op_name of its own (the async copies and slices of its memory-space
assignment, layout copies) goes where the first instruction that
consumes its result goes: it runs on that one's behalf.  A loop's, a
branch's or a call's own event is no operation and is neither placed
nor counted among the unplaced: its body's events are
(`harness.trace.operations`), so the phases add up to the device's busy
time and not to more.  Where less
than `PLACED_SHARE` of the device's busy time finds a phase (a stale
executable loaded from a cache, scopes lost to a refactoring, a program
that never had them) there is no number, and one `scope-note` line on
stderr says why.
"""
from __future__ import annotations

import re
import sys

from harness import trace

PHASES = ("forward", "backward", "optimizer")
PLACED_SHARE = 0.9
DEFINITION = re.compile(r'^\s*(?:ROOT\s+)?%?([^\s=]+)\s*=\s*(.*)$')
OP_NAME = re.compile(r'\bop_name="((?:[^"\\]|\\.)*)"')
OPERAND = re.compile(r'%([^\s,(){}]+)')


def phase_of(op_name):
    if "transpose(" in op_name:
        return "backward"
    if "forward" in op_name:
        return "forward"
    if "/optimizer/" in op_name:
        return "optimizer"
    return None


def instruction_phases(program_text):
    """{instruction name: phase} from the compiled text: the phase an
    instruction's own op_name names; for one with no op_name at all,
    that of the first instruction (in the text's order, through others
    without one) that uses it."""
    phases, users, unnamed = {}, {}, []
    for line in program_text.splitlines():
        found = DEFINITION.match(line)
        if not found:
            continue
        name, rest = found.groups()
        op_name = OP_NAME.search(rest)
        phase = phase_of(op_name.group(1)) if op_name else None
        if phase:
            phases[name] = phase
        elif not op_name:
            unnamed.append(name)
        for operand in OPERAND.findall(rest.partition(", metadata=")[0]):
            users.setdefault(operand, []).append(name)
    for name in unnamed:
        queue, seen = [name], {name}
        while queue and name not in phases:
            for user in users.get(queue.pop(0), ()):
                if user in phases:
                    phases[name] = phases[user]
                    break
                if user not in seen:
                    seen.add(user)
                    queue.append(user)
    return phases


def split(record, phases, step_program):
    """({phase: ms a step}, placed share, the heaviest unplaced events)
    from a trace record and an {instruction: phase} map: summed device
    time of each phase's events inside the window, averaged over the
    devices, over the steps that start in it."""
    steps = len(trace.step_starts(record, step_program))
    total = dict.fromkeys(PHASES, 0)
    unplaced = {}
    for name, inside in trace.operations(record):
        phase = phases.get(name.split(" ", 1)[0])
        if phase:
            total[phase] += inside
        else:
            unplaced[name] = unplaced.get(name, 0) + inside
    placed, lost = sum(total.values()), sum(unplaced.values())
    if not steps or not placed + lost:
        return None, 0.0, []
    per_step_ms = 1e6 * len(record["devices"]) * steps
    heaviest = sorted(unplaced.items(), key=lambda kv: -kv[1])[:5]
    return ({phase: ns / per_step_ms for phase, ns in total.items()},
            placed / (placed + lost),
            [[name, ns / per_step_ms] for name, ns in heaviest])


def phase_ms(run):
    """{phase: ms a step} of this run's traced stretch, or None.  The
    compiled text is parsed once a run (every `run.program_text()` is a
    `.lower().compile()` of the whole step) and the result kept on
    `run` for the other two readers."""
    if not hasattr(run, "phase_ms"):
        run.phase_ms = None
        if run.trace is not None and run.program_text is not None:
            values, share, heaviest = split(
                run.trace, instruction_phases(run.program_text()),
                run.traffic["step_program"])
            if values and share >= PLACED_SHARE:
                run.phase_ms = values
            else:
                print(f"scope-note {run.cell['name']}: {100 * share:.1f} % "
                      "of the device's busy time finds a phase in the "
                      f"compiled step's op_names, under "
                      f"{100 * PLACED_SHARE:.0f} %: fwd_ms, bwd_ms and "
                      "optimizer_ms are left out; heaviest unplaced "
                      f"events (ms a step): {heaviest!r}", file=sys.stderr)
    return run.phase_ms


def read(run, phase):
    values = phase_ms(run)
    return values and values[phase]
