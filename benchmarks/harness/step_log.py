"""The program's own record of what the host did in each `step()`:
`mxnet_tpu.parallel.data_parallel.step_log()`, one tuple a call,
(trainer serial, t, begin_ns, put_ns, args_ns, enqueue_ns, put_bytes).

The readers take its LAST `window.count` records: exactly the untraced
window's steps, since nothing calls `step()` between the window's close
and the readers.  That is the window `host_dispatch_ms` sums, so the
three phases add up to it, less the NDArray unwrapping and the spans'
own cost.  A program without the log (the parent of the PR that brought
it) gives no number."""
from __future__ import annotations


def window_records(run):
    """The window's records, or None unless that many records of one
    trainer with consecutive `t` are there."""
    window = run.spans.get("window")
    if not window or not window["count"]:
        return None
    try:
        from mxnet_tpu.parallel import data_parallel
        records = data_parallel.step_log(last=window["count"])
    except (ImportError, AttributeError):
        return None
    if len(records) != window["count"] or len({r[0] for r in records}) != 1:
        return None
    first = records[0][1]
    if [r[1] for r in records] != list(range(first, first + len(records))):
        return None
    return records


def mean_ms(run, field):
    records = window_records(run)
    if records is None:
        return None
    return sum(r[field] for r in records) / len(records) / 1e6
