"""Model FLOPs utilization of the training step, in percent: model FLOPs
per step, from ``train_flops_per_step`` of the reference that the cell's
configuration names (attention included, recomputation not), times
steps per second of the window, over one chip's bf16 peak
(``bench/peaks.json``).  A reference without ``train_flops_per_step``
leaves the metric out."""


def read(run):
    steps, window_s = run.counter("steps"), run.counter("window_s")
    flops_per_step = getattr(run.cell.reference, "train_flops_per_step",
                             None)
    if not steps or not window_s or not run.peak or flops_per_step is None:
        return None
    tr = run.cell.traffic
    per_step = flops_per_step(run.cell.config["model"], tr["batch"],
                              tr["seq"])
    return 100.0 * per_step * steps / window_s \
        / (run.peak["bf16_flops_per_s"] * run.cell.chips)
