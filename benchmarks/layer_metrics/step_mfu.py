"""The whole step's share of the chips' peak: model FLOPs a step needs,
from the configuration's own function of its published sizes, over the
mean step period of the run's untraced window (all its steps over all
its seconds, idle gaps included) and the peak of the cell's chips.

Not over the traced stretch's period: the profiler slows the host, and
where the host's step call is a large part of the step (ResNet's 77 MB
batch) the traced steps take twice as long as the untraced ones."""


def read(run):
    window = run.spans.get("window")
    if not window or not window["count"]:
        return None
    period = window["seconds"] / window["count"]
    flops = run.config_mod.model_flops_per_step(run.config, run.traffic)
    return 100.0 * flops / period / (
        run.peaks["peak_flops_bf16"] * run.chips)
