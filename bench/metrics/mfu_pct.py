"""The whole step's share of the chip's peak: the graph's operations per
image times the images completed per second (host clock, traced run) over
the peak of the tier (``bench/peaks.json``)."""


def read(run: dict):
    if not run["images_per_s"]:
        return None
    return 100.0 * run["ops_per_image"] * run["images_per_s"] \
        / run["peak_ops"]
