"""`run_experiment` under the profiler: its chunk loop opens the
harness's host spans, so `tracing` reads a profile of a user's run as it
reads the benchmark's, and its trace's summary carries the compile log."""
import bench_paths
import importlib.util
import os

import jax

import tracing as T


def _trace_config():
    path = os.path.join(bench_paths.ROOT, "tests", "golden", "make_goldens.py")
    spec = importlib.util.spec_from_file_location("make_goldens", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.trace_config()


def test_run_experiment_opens_the_harness_spans(tmp_path):
    from repro.fl import run_experiment
    from repro.obs import read_trace

    cfg, fl, data = _trace_config()
    trace = str(tmp_path / "run.jsonl")
    with jax.profiler.trace(str(tmp_path / "prof")):
        run_experiment("fedavg", cfg, fl, data, num_rounds=4, eval_every=2,
                       steps_per_epoch=1, seed=0, verbose=False, trace=trace,
                       chunk_rounds=2)
    host = T.extract(str(tmp_path / "prof"))["host"]
    assert [n for n, _, _ in host] == list(T.HOST_SPANS) * 2
    # each span ends before the next one starts
    ends = [s + d for _, s, d in host]
    assert all(e <= s for e, (_, s, _) in zip(ends, host[1:]))
    summary = read_trace(trace)[-1]
    log = summary["compile_log"]
    assert summary["type"] == "summary"
    assert set(log) == {"trace_s", "compile_s", "compiles", "cache_hits"}
    assert log["compiles"] >= 1 and 0 < log["trace_s"]
