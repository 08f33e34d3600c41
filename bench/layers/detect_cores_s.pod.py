"""Seconds of core detection per verdict: the program's
``sloth.detect.cores`` span (stage-grouped outliers over the compute
patterns), over the ``pod.verdict`` spans in the window."""

import program_spans


def read(run):
    return program_spans.seconds_per_verdict(run, "sloth.detect.cores")
