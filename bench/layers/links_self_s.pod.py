"""Seconds of link detection outside its EM per verdict: the program's
``sloth.detect.links`` span less its ``.em`` child (the path matrix, the
per-window regression and the Gamma scoring), over the ``pod.verdict``
spans in the window."""

import program_spans


def read(run):
    return program_spans.self_seconds_per_verdict(
        run, "sloth.detect.links", "sloth.detect.links.em")
