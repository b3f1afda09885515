"""Share of their roofline the arena kernels reach: the least time the
chip could take for the graph's ops (per op the larger of operations over
peak and bytes over HBM bandwidth, ``bench/opcount.py``) over the kernels'
device time, both for the traced calls."""


def read(run: dict):
    t = run["trace"]
    if t is None or not t.kernel_s:
        return None
    return 100.0 * run["ideal_s_per_call"] * t.calls / t.kernel_s
