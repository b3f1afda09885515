"""Device-idle milliseconds per traced call that the trace files under the
executor's host preparation: its ``dmo.resolve``, ``dmo.legalise``,
``dmo.seed_arena``, ``dmo.launch`` and ``dmo.gather`` spans (parameters
and weight list, layouts and lowered specs, the arena seed, the program
lookup and dispatch, the output gather). None where the program writes
no such span."""

PHASES = ("dmo.resolve", "dmo.legalise", "dmo.seed_arena", "dmo.launch",
          "dmo.gather")


def read(run: dict):
    t = run["trace"]
    if t is None:
        return None
    idle = dict(t.idle_gaps)
    if not any(p in idle for p in PHASES):
        return None
    return 1e3 * sum(idle.get(p, 0.0) for p in PHASES) / t.calls
