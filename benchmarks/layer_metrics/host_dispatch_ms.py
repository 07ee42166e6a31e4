"""Host milliseconds a `trainer.step(x, y)` call takes (enqueue and the
put of the batch, no block): the harness's own clock around every call
of the window, summed, over the calls.  The sum spans the whole window,
so the half millisecond the host's clock is off by does not show."""


def read(run):
    span = run.spans.get("step_call")
    if not span or not span["count"]:
        return None
    return span["seconds"] / span["count"] * 1000.0
