"""The trace reduction on hand-made events."""
import bench_paths  # noqa: F401  (bench/ and src/ on the path)
import pytest

import tracing as T

HLO = """HloModule jit_multi_fn, entry_computation_layout={()->()}

ENTRY %main {
  %fusion.1 = bf16[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(multi_fn)/while/body/stage:phase_e/conv"}
  %select_topk.3 = (f32[16,128]{1,0}) custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(multi_fn)/while/body/stage:score_select/select_topk"}
  %while.2 = (s32[]) while(%t), condition=%c, body=%b, metadata={op_name="jit(multi_fn)/while/body/stage:phase_e/while"}
  ROOT %copy.9 = f32[8]{0} copy(%x)
}
"""


M = "jit_multi_fn"


def events():
    ms = 1e6
    return {
        "devices": {"/device:TPU:0": [
            ["while.2", 1 * ms, 4 * ms, M],       # holds the next two
            ["fusion.1", 1 * ms, 2 * ms, M],
            ["fusion.1", 3 * ms, 2 * ms, M],
            ["select_topk.3", 6 * ms, 1 * ms, M],
            ["copy.9", 8 * ms, 1 * ms, "jit_other"],
            ["fusion.1", 20 * ms, 5 * ms, M],    # after the window
        ]},
        "host": [["dispatch", 0.0, 0.5 * ms], ["wait", 0.5 * ms, 9 * ms],
                 ["fetch_metrics", 9.5 * ms, 0.5 * ms]],
    }


def test_op_names():
    names = T.op_names(HLO)
    assert names["fusion.1"] == ("phase_e", None)
    assert names["select_topk.3"] == ("score_select", "select_topk")
    assert names["copy.9"] == (None, None)


def test_reduce():
    assert T.module_name(HLO) == M
    assert T.instruction("%fusion.1 = bf16[8]{0} fusion(%p)") == "fusion.1"
    r = T.reduce(events(), T.op_names(HLO), M)
    assert r["window_s"] == pytest.approx(0.010)
    assert r["busy_s"] == pytest.approx(0.006)
    assert r["stage_s"] == pytest.approx({"phase_e": 0.004,
                                          "score_select": 0.001})
    assert r["kernel_s"] == pytest.approx({"select_topk": 0.001})
    assert r["device_ops"][0] == ["phase_e/fusion.1", pytest.approx(0.004)]
    # idle: [0,1) in dispatch/wait, [5,6), [7,8) and [9,10) ms
    assert sum(g for _, g in r["idle_gaps"]) == pytest.approx(0.004)
    assert {n for n, _ in r["idle_gaps"]} <= {"dispatch", "wait",
                                              "fetch_metrics"}


def test_reduce_needs_the_window_spans():
    ev = events()
    ev["host"] = ev["host"][:2]
    with pytest.raises(ValueError):
        T.reduce(ev, {}, M)
