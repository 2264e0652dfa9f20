"""North-star train benchmark at larger scales (BASELINE.json target 2:
tokens/sec/chip toward the 7B class; ref: release/air_tests/air_benchmarks
methodology — fixed workload, emitted throughput).

    python release/train_benchmark.py --preset 1b --batch 4 --seq 1024

Emits one JSON line per preset. Needs a TPU: run it on the chip
machine. One process, which holds the chip; it starts no child.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="1b")
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--seq", type=int, default=None)
    args = ap.parse_args()

    from bench import run_train_bench

    print(json.dumps(run_train_bench(args.preset, batch=args.batch,
                                     seq=args.seq)), flush=True)


if __name__ == "__main__":
    main()
