"""Benchmark of PFedDST's federated rounds on the TPU.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of BENCHMARK.json on the chips of this machine and prints,
as the last line of standard output, one JSON object: `correct`,
`attempted` (rounds in the window), `failed` (rounds whose loss is not
finite), `metrics` (the cell's end-to-end metrics with `--trace 0`, its
per-layer metrics with `--trace 1`), `device`, with `--trace 1` a
`breakdown`, and last the `checks`: each number compared with the
reference, beside its limit. Without a TPU whose kind is in
bench/peaks.json, or with fewer chips than the cell asks for, it exits 3
and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(ROOT, "src"))


def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def chips(n: int):
    """The first n TPU devices and their peak table entry; exits 3 when
    there are fewer, or their kind has no entry."""
    import jax

    with open(os.path.join(HERE, "peaks.json")) as fh:
        peaks = json.load(fh)
    devs = jax.devices()
    kind = devs[0].device_kind
    if devs[0].platform != "tpu" or len(devs) < n or kind not in peaks:
        print(f"bench: needs {n} TPU chip(s) of a kind in peaks.json, "
              f"found {len(devs)} {devs[0].platform} ({kind})",
              file=sys.stderr)
        sys.exit(3)
    return devs[:n], peaks[kind]


def main(argv=None) -> int:
    args = parse(argv)
    if args.seed < 0:
        sys.exit("bench: --seed must be a whole number >= 0")
    import workload

    cell = workload.cell(args.workload, ROOT)
    devices, peak = chips(cell["chips"])
    import harness

    harness.enable_cache()
    result = harness.run(cell, seed=args.seed, seconds=args.seconds,
                         trace=bool(args.trace), t0=T0, devices=devices,
                         peak=peak)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
