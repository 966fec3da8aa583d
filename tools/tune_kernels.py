#!/usr/bin/env python
"""Offline Pallas-kernel autotuner — pre-populate the tuning cache.

Run this once per environment (and per model shape class) so serving
processes start with every (kernel, bucket) winner on disk and never
measure candidates online:

    # tune the ladder a GPT-style decode service will trace
    python tools/tune_kernels.py --batch-ladder 1,2,4,8 \
        --len-ladder 128,256,512 --num-heads 8 --head-dim 64 \
        --units 512 --families flash_fwd,flash_bwd,layer_norm

    # then serve with the tuned tier on
    MXTPU_TUNE=1 python serve_my_model.py   # Predictor/DecodeEngine
                                            # warmup preloads winners

On a CPU-only box pass --interpret to exercise the Pallas paths through
the interpreter (mechanism check; block winners only transfer from real
hardware). The cache lands at ``context.tuning_cache_path()`` (override:
``MXTPU_TUNE_CACHE``), keyed by ``context.env_signature()`` — a
cache tuned under one environment is never replayed into another.

Exit code 0 on success; prints one JSON line per tuned spec and a
summary line at the end.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _ints(s):
    return tuple(int(x) for x in s.split(",") if x.strip())


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--batch-ladder", type=_ints, default=(1, 2, 4, 8))
    ap.add_argument("--len-ladder", type=_ints, default=(128, 256, 512))
    ap.add_argument("--num-heads", type=int, default=8)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--units", type=int, default=512,
                    help="d_model for the row-wise kernels (LayerNorm "
                         "rows are batch*len wide, units deep)")
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--families", default="flash_fwd,layer_norm",
                    help="comma list from: flash_fwd, flash_bwd, "
                         "layer_norm, softmax")
    ap.add_argument("--no-seg", action="store_true",
                    help="tune the plain attention variant instead of "
                         "the segment-ids one the serving prefill uses")
    ap.add_argument("--trials", type=int, default=None,
                    help="measurement rounds per candidate "
                         "(default MXTPU_TUNE_TRIALS or 3)")
    ap.add_argument("--max-per-axis", type=int, default=3,
                    help="power-of-two block candidates per axis")
    ap.add_argument("--interpret", action="store_true",
                    help="set MXTPU_PALLAS_INTERPRET=1 (CPU mechanism "
                         "check)")
    ap.add_argument("--cache", default=None,
                    help="override the cache path (MXTPU_TUNE_CACHE)")
    args = ap.parse_args(argv)

    if args.interpret:
        os.environ["MXTPU_PALLAS_INTERPRET"] = "1"
    if args.cache:
        os.environ["MXTPU_TUNE_CACHE"] = args.cache

    from mxnet_tpu import tune

    families = tuple(f.strip() for f in args.families.split(",")
                     if f.strip())
    bad = [f for f in families if f not in
           ("flash_fwd", "flash_bwd", "layer_norm", "softmax")]
    if bad:
        ap.error(f"unknown kernel families: {bad}")
    specs = tune.ladder_specs(args.batch_ladder, args.len_ladder,
                              args.num_heads, args.head_dim, args.units,
                              dtype=args.dtype, seg=not args.no_seg,
                              families=families)

    def emit(line):
        print(line, flush=True)

    results = tune.autotune(specs, trials=args.trials,
                            max_per_axis=args.max_per_axis,
                            verbose=emit)
    path = tune.save()
    wins = sum(1 for r in results if r["winner"] not in ("default",))
    print(json.dumps({
        "tuned_specs": len(results),
        "non_default_winners": wins,
        "measurements": tune.status()["measurements"],
        "cache_path": path,
        "next": "serve with MXTPU_TUNE=1; warmup preloads these winners",
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
