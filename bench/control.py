"""The control of the comparison: the reference one precision step down.

    python3 bench/control.py --workload <cell> --seeds 1 2 3

For each seed it makes the cell's input pool and parameters as a run does,
puts the reference computed one step below the configuration's precision
(int4 weights for int8, bfloat16 operands for float32) in the program's
place for every call of the pool, and prints the numbers ``bench/check.py``
compares, beside the configuration's limits. A sound limit sits below at
least one of the readings this prints. Runs on the host; needs no chip.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import check, loadgen  # noqa: E402


def reading(cell, seed: int) -> dict:
    """Each compared number's widest reading of the control's answers over
    the cell's pool."""
    import numpy as np
    cfg, traffic = cell.config, cell.traffic
    ref = cell.reference()
    res = int(cfg["resolution"])
    pool = loadgen.make_pool(traffic, (res, res, 3), cfg["dtype"], seed)
    images = loadgen.pool_images(pool, int(traffic["batch"]))
    calib = ref.calibrate(cfg)
    w, q = ref.make_params(cfg, seed, calib)
    wc, qc = ref.make_params(cfg, seed, calib, control=True)
    answers = [(i, ref.predict(cfg, wc, qc, img, control=True))
               for i, img in enumerate(images)]
    refs = {i: np.asarray(ref.predict(cfg, w, q, img))
            for i, img in enumerate(images)}
    return check.compare(cfg["check"], answers, refs)["numbers"]


def main(argv=None) -> None:
    from bench import registry
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = registry.load_cell(args.workload)
    for seed in args.seeds:
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "numbers": reading(cell, seed),
                          "limits": cell.config["check"]}), flush=True)


if __name__ == "__main__":
    main()
