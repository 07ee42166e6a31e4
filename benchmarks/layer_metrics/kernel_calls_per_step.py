"""Mosaic (Pallas) kernels in the compiled step program: occurrences of
`tpu_custom_call` in its text, as chip_smoke.py counts them.  Guards a
silent fall to the XLA form: a cell that lists this metric and reads 0
fails loudly."""


def read(run):
    if run.program_text is None:
        return None
    count = run.program_text().count("tpu_custom_call")
    if count == 0:
        raise RuntimeError(
            f"{run.cell['name']}: no tpu_custom_call in the compiled step: "
            "the Pallas kernels fell to the XLA form")
    return count
