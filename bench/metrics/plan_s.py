"""Seconds of ``compile()`` (planning, host clock) in the run's set-up."""


def read(run: dict):
    return run["plan_s"]
