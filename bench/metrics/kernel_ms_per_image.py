"""Device milliseconds of Pallas kernels per image, from the trace."""


def read(run: dict):
    t = run["trace"]
    if t is None or not t.kernel_count:
        return None
    return t.kernel_s * 1e3 / (t.calls * run["batch"])
