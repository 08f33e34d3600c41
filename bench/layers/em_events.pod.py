"""Path events the link EM solves per verdict: the ``events`` counter
(rows of the events x links path matrix) of the program's
``sloth.detect.links`` spans, over the ``pod.verdict`` spans in the
window.  The EM's cost grows with it; expiring old evidence would cut
it."""

import program_spans


def read(run):
    return program_spans.count_per_verdict(run, "sloth.detect.links",
                                           "events")
