"""The device's idle share of the traced window, in percent: 1 minus the
union of the device's operation intervals over the length of the window
that the trace covers, up to the last operation it kept
(``bench/devtrace.py``)."""


def read(run):
    if not run.trace or run.trace.get("idle_share") is None:
        return None
    return 100.0 * run.trace["idle_share"]
