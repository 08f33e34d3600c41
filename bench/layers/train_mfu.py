"""Model FLOPs utilization of the training step, in percent: model FLOPs
per step (``bench/flops.py``, attention included, recomputation not)
times steps per second of the window, over one chip's bf16 peak
(``bench/peaks.json``)."""

import flops


def read(run):
    steps, window_s = run.counter("steps"), run.counter("window_s")
    if not steps or not window_s or not run.peak:
        return None
    cfg, tr = run.cell.config, run.cell.traffic
    per_step = flops.train_flops_per_step(cfg["model"], tr["batch"],
                                          tr["seq"])
    return 100.0 * per_step * steps / window_s \
        / (run.peak["bf16_flops_per_s"] * run.cell.chips)
