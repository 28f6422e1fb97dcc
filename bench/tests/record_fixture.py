"""Record a chip trace of the tiny pfeddst cell as a test fixture.

    python3 bench/tests/record_fixture.py [OUT]

Compiles the chunk program of `bench_paths.tiny_cell("pfeddst.m16")`
(two rounds a chunk), runs the first chunk, then one more chunk under
the profiler with the harness's `dispatch`/`wait`/`fetch_metrics` spans,
and writes what `tracing.extract` read with the stage, kernel and part
names of the compiled chunk and the numbers reduced from them (default
OUT: fixtures/trace_pfeddst_parts.json). Run it on a TPU: elsewhere the
trace holds no device operations.
"""
import bench_paths  # noqa: F401  (bench/ and src/ on the path)
import json
import os
import shutil
import sys

import jax
import jax.numpy as jnp

import harness
import parts
import tracing
import workload

HERE = os.path.dirname(os.path.abspath(__file__))


def record(seed: int = 7) -> dict:
    prog = harness.Program(bench_paths.tiny_cell("pfeddst.m16"))
    state, _, data, keys = prog.first_chunk(seed)
    trace_dir = os.path.join(workload.ROOT, ".bench_trace_fixture")
    shutil.rmtree(trace_dir, ignore_errors=True)
    with prog.mesh_ctx():
        jax.profiler.start_trace(trace_dir)
        with jax.profiler.TraceAnnotation("dispatch"):
            state, stacked = prog.compiled(state, data, keys["rounds"],
                                           jnp.int32(prog.chunk))
        with jax.profiler.TraceAnnotation("wait"):
            jax.block_until_ready((state, stacked))
        with jax.profiler.TraceAnnotation("fetch_metrics"):
            jax.device_get(stacked)
        jax.profiler.stop_trace()
    events = tracing.extract(trace_dir)
    shutil.rmtree(trace_dir, ignore_errors=True)
    hlo = prog.compiled.as_text()
    names, module = tracing.op_names(hlo), tracing.module_name(hlo)
    # only the instructions the trace ran
    ran = {op[0] for ops in events["devices"].values() for op in ops}
    names = {k: v for k, v in names.items() if k in ran}
    part_names = {k: v for k, v in parts.op_parts(hlo).items() if k in ran}
    reduced = tracing.reduce(events, names, module)
    return {"events": events, "names": names, "module": module,
            "reduced": reduced, "parts": part_names,
            "part_s": parts.part_seconds(events, part_names, module)}


if __name__ == "__main__":
    out = sys.argv[1] if len(sys.argv) > 1 else os.path.join(
        HERE, "fixtures", "trace_pfeddst_parts.json")
    fx = record()
    with open(out, "w") as fh:
        json.dump(fx, fh)
    print(json.dumps({"out": out, "device": jax.devices()[0].device_kind,
                      "stage_s": fx["reduced"]["stage_s"],
                      "part_s": fx["part_s"]}))
