"""Device-idle milliseconds per traced call that the trace files under the
executor's ``dmo.upload`` span: the weights and the arena copied to the
device. None where the program writes no such span."""

PHASES = ("dmo.upload",)


def read(run: dict):
    t = run["trace"]
    if t is None:
        return None
    idle = dict(t.idle_gaps)
    if not any(p in idle for p in PHASES):
        return None
    return 1e3 * sum(idle.get(p, 0.0) for p in PHASES) / t.calls
