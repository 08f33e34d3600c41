"""Seconds of the link EM per verdict: the program's
``sloth.detect.links.em`` span (the solve that iterates over each path
event's route links), over the ``pod.verdict`` spans in the window."""

import program_spans


def read(run):
    return program_spans.seconds_per_verdict(run, "sloth.detect.links.em")
