#!/usr/bin/env python3
"""Readings of the control for a configuration's limit.

    python3 bench/control.py --config resnet50-valid --seeds 1 2 3

For each seed: the cell's weights and image pool, the plain float32
reference, and the control in the program's place: the same reference one
precision below the configuration's ``highest``, as ``Precision.HIGH``
(three bfloat16 passes on a TPU) and as those passes written out
(``bf16x3``). Prints, per seed, the number that ``serve.check`` compares
(largest |control − reference| over max |reference| per image), and its
median over the images, as one JSON line. A limit is set between the
largest reading of sound runs of the program and the smallest of these.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def readings(cfg, seed: int, images: int):
    """Per control arithmetic: the largest and the median reading."""
    import numpy as np
    from bench import reference
    w = reference.make_weights(cfg, seed)
    xs = reference.make_images(cfg, seed, images)
    ref = reference.forward(cfg, w, xs)
    out = {}
    for arith in ("high", "bf16x3"):
        ctl = reference.forward(cfg, w, xs, arith)
        err = (np.abs(ctl - ref).reshape(len(xs), -1).max(1)
               / np.abs(ref).reshape(len(xs), -1).max(1))
        out[arith] = [float(err.max()), float(np.median(err))]
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--images", type=int, default=64)
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import registry
    cfg = registry.load_json("configs", args.config)
    import jax
    print(f"device {jax.devices()[0].device_kind}", file=sys.stderr)
    for seed in args.seeds:
        print(json.dumps({"config": args.config, "seed": seed,
                          "max_and_median": readings(cfg, seed,
                                                     args.images)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
