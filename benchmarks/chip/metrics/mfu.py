"""Local-training FLOPs the window's uploads required (per upload, epochs x
samples x the forward and backward FLOPs of ``counts.train_flops_per_upload``,
from the configuration's widths) over the window's seconds times the chip's
bf16 peak. The clients train in float32, for which no peak is published;
the bf16 peak bounds it from above."""


def read(run):
    if run.peaks is None or not run.train_flops:
        return None
    return 100.0 * run.train_flops / (run.window_s * run.peaks["bf16_flops_per_s"])
