"""The trace reduction on traces recorded on a TPU v5e: one chunk of two
rounds of each strategy at the tests' tiny size, as `tracing.extract`
read them from the profiler, with the stage and kernel names of the
compiled chunk."""
import bench_paths  # noqa: F401  (bench/ and src/ on the path)
import json
import os

import pytest

import tracing as T

HERE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")
STAGES = {
    "pfeddst": {"score_select", "aggregate", "phase_e", "phase_h",
                "update_context"},
    "dispfl": {"apply_masks", "plan_gossip", "local_train", "aggregate_mix",
               "evolve_masks"},
}
KERNELS = {"pfeddst": {"select_topk"},
           "dispfl": {"gossip_mix", "mask_evolve_threshold",
                      "mask_evolve_apply"}}


def load(strategy):
    with open(os.path.join(HERE, f"trace_{strategy}.json")) as fh:
        return json.load(fh)


@pytest.mark.parametrize("strategy", ["pfeddst", "dispfl"])
def test_recorded_trace(strategy):
    fx = load(strategy)
    names = {k: tuple(v) for k, v in fx["names"].items()}
    r = T.reduce(fx["events"], names, fx["module"])
    assert r["devices"] == 1
    assert 0 < r["busy_s"] < r["window_s"]
    # every stage of the round is found, and the stages hold nearly all
    # of the round program's device time
    assert STAGES[strategy] <= set(r["stage_s"])
    program = sum(d for ops in fx["events"]["devices"].values()
                  for n, s, d, mod in T._leaves(ops) if mod == fx["module"])
    assert sum(r["stage_s"].values()) >= 0.9 * program * 1e-9
    assert set(r["kernel_s"]) == KERNELS[strategy]
    assert all(v > 0 for v in r["kernel_s"].values())
    # the numbers read on the chip when the trace was recorded
    for k in ("window_s", "busy_s"):
        assert r[k] == pytest.approx(fx["reduced"][k], rel=1e-9)
    assert r["stage_s"] == pytest.approx(fx["reduced"]["stage_s"],
                                         rel=1e-9)
