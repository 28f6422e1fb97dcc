"""Readings that the limits of a cell's correctness check are set from.

    python bench/calibrate.py --workload <cell> --seeds 12 --control 3 \
        --faults half_batch,answer [--faults-only] --out chiprun_out/cal.json

In one process, on the chip: for each seed, the program's first chunk
and the reference over the same rounds, compared as a run compares them
(the lower readings); on the first `--control` seeds the control, the
reference in the precision below the configuration's, in the program's
place (the upper readings; the model's `control_quant`); and
each planted fault of the program on the same seeds. The benchmark's own
runs never do this. Prints one JSON object per reading, then the table.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(os.path.dirname(HERE), "src"))

import faults  # noqa: E402
import harness  # noqa: E402
import workload  # noqa: E402


def readings(cell, seeds, n_control, fault_names, out=print, n_fault=3,
             sound=True):
    """Program and reference on every seed, the control on the first
    `n_control`, each planted fault on the first `n_fault` (its own
    Program, built after the sound one is gone). Without `sound`, only
    the faults, each against a reference of its own seed."""
    rows = []
    refs = {}
    prog = harness.Program(cell)
    for i, seed in enumerate(seeds if sound else []):
        t = time.perf_counter()
        state, stacked, data, keys = prog.first_chunk(seed)
        snap = prog.snapshot(state, stacked, keys)
        del state, stacked
        ref = refs[seed] = prog.reference(data, keys)
        row = {"kind": "program", "seed": seed,
               **prog.ref.compare(snap, ref),
               "s": time.perf_counter() - t}
        out(json.dumps(row))
        rows.append(row)
        if i < n_control:
            ctl = prog.reference(data, keys, quant=prog.model.control_quant)
            row = {"kind": "control", "seed": seed,
                   **prog.ref.compare(ctl, ref)}
            out(json.dumps(row))
            rows.append(row)
        del data
    del prog
    for name in fault_names:
        with faults.planted(name, cell["sizes"]["strategy"]):
            bad = harness.Program(cell)
            for seed in seeds[:n_fault]:
                state, stacked, data, keys = bad.first_chunk(seed)
                snap = bad.snapshot(state, stacked, keys)
                del state, stacked
                if seed not in refs:
                    refs[seed] = bad.reference(data, keys)
                row = {"kind": f"fault:{name}", "seed": seed,
                       **bad.ref.compare(snap, refs[seed])}
                out(json.dumps(row))
                rows.append(row)
                del data
            del bad
    return rows


def table(rows) -> dict:
    out = {}
    for r in rows:
        for k, v in r.items():
            if k in ("kind", "seed", "s"):
                continue
            d = out.setdefault(k, {})
            d.setdefault(r["kind"], []).append(v)
    return {k: {kind: {"max": max(v), "min": min(v), "n": len(v)}
                for kind, v in d.items()} for k, d in out.items()}


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, default=12)
    p.add_argument("--first-seed", type=int, default=3_000_000_000)
    p.add_argument("--control", type=int, default=3)
    p.add_argument("--faults", default="")
    p.add_argument("--faults-only", action="store_true",
                   help="skip the sound runs and the control")
    p.add_argument("--out", default="")
    a = p.parse_args()
    cell = workload.cell(a.workload)
    harness.enable_cache()
    seeds = [a.first_seed + 7919 * i for i in range(a.seeds)]
    rows = readings(cell, seeds, a.control,
                    [f for f in a.faults.split(",") if f],
                    sound=not a.faults_only)
    tab = table(rows)
    print(json.dumps(tab))
    if a.out:
        os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
        with open(a.out, "w") as fh:
            json.dump({"rows": rows, "table": tab}, fh, indent=1)


if __name__ == "__main__":
    main()
