"""Pallas kernel events on the device per call, from the trace."""


def read(run: dict):
    t = run["trace"]
    if t is None or not t.kernel_count:
        return None
    return t.kernel_count / t.calls
