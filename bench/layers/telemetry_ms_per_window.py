"""Milliseconds of ``StepTelemetry.flush`` per telemetry window in the
measured window: simulating the peers, the pod verdict and the plan."""


def read(run):
    spans = run.rec.within("telemetry", run.t0, run.t1)
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
