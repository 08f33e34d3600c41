"""Milliseconds of host work per training step before the device gets
the step: the launcher's ``train.data`` (the next batch onto the device)
and ``train.dispatch`` (the jitted step's call) spans, per step in the
window."""

import program_spans


def read(run):
    return program_spans.ms_per_step(run, ("train.data", "train.dispatch"))
