"""Seconds in the program's failrank layer per verdict, from the spans the
harness puts around its calls (``bench/drivers/pod.py`` ``instrument``)."""


def read(run):
    n = run.counter("verdicts")
    spans = run.rec.within("failrank", run.t0, run.t1)
    if not n or not spans:
        return None
    return sum(spans) / n
