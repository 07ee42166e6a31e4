"""The arithmetic of a roofline's least work that names no
configuration.  A configuration's work functions (`configs/<config>.py`)
give the shapes; the peaks are in `peaks.json`."""
from __future__ import annotations

BYTES_PER_ELEMENT = 2


def dense_work(tokens, products):
    """(FLOPs, bytes) of dense products, each an (inputs, outputs) pair
    of a weight, on `tokens` rows, forward and backward, bf16: 2 FLOPs
    a weight and row forward, twice that backward (the input's and the
    weight's gradient); in each of the three passes the weight, the
    product's input and its output move once; nothing recomputed."""
    weights = sum(i * o for i, o in products)
    rows = sum(i + o for i, o in products)
    return (3 * 2 * tokens * weights,
            3 * BYTES_PER_ELEMENT * (weights + tokens * rows))
