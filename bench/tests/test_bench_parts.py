"""The `part:` scopes inside the engine's stages: read from the compiled
chunk on the CPU, reduced from hand-made events, and reduced from a
trace recorded on a TPU v5e (one chunk of two rounds of the tiny pfeddst
cell, bench/tests/record_fixture.py), and read by the per-layer metrics
`probe_ms` and `select_ms`."""
import bench_paths
import json
import os

import pytest

import harness
import parts as P
import tracing as T
import workload

HERE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")
STAGES = {"score_select", "aggregate", "phase_e", "phase_h",
          "update_context"}
SCORE_PARTS = {"score_select/eq6_probe", "score_select/eq79_select",
               "score_select/telemetry"}
M = "jit_multi_fn"
ROOT_OP = "jit(multi_fn)/while/body/closed_call"

HLO = f"""HloModule jit_multi_fn, entry_computation_layout={{()->()}}

ENTRY %main {{
  %fusion.1 = bf16[8]{{0}} fusion(%p), kind=kLoop, metadata={{op_name="{ROOT_OP}/stage:score_select/part:eq6_probe/vmap(conv)"}}
  %reshape.2 = f32[8]{{0}} reshape(%q), metadata={{op_name="{ROOT_OP}/stage:score_select/part:eq6_probe/jit(loss)/reshape"}}
  %select_topk.3 = (f32[16,128]{{1,0}}) custom-call(%a), custom_call_target="tpu_custom_call", metadata={{op_name="{ROOT_OP}/stage:score_select/part:eq79_select/select_topk"}}
  %fusion.4 = f32[] fusion(%r), kind=kLoop, metadata={{op_name="{ROOT_OP}/stage:score_select/part:telemetry/reduce_sum"}}
  %fusion.5 = f32[] fusion(%s), kind=kLoop, metadata={{op_name="{ROOT_OP}/stage:score_select/div"}}
  %fusion.6 = bf16[8]{{0}} fusion(%t), kind=kLoop, metadata={{op_name="{ROOT_OP}/stage:phase_e/conv"}}
  ROOT %fusion.7 = f32[] fusion(%u), kind=kLoop, metadata={{op_name="{ROOT_OP}/stage:update_context/part:telemetry/reduce_sum"}}
}}
"""


def events():
    ms = 1e6
    return {
        "devices": {"/device:TPU:0": [
            ["fusion.1", 1 * ms, 2 * ms, M],
            ["reshape.2", 3 * ms, 1 * ms, M],
            ["select_topk.3", 4 * ms, 0.5 * ms, M],
            ["fusion.4", 4.5 * ms, 0.25 * ms, M],
            ["fusion.5", 4.75 * ms, 0.25 * ms, M],
            ["fusion.6", 5 * ms, 3 * ms, M],
            ["fusion.7", 8 * ms, 0.5 * ms, M],
            ["fusion.1", 8.5 * ms, 1 * ms, "jit_other"],
            ["fusion.1", 20 * ms, 5 * ms, M],    # after the window
        ]},
        "host": [["dispatch", 0.0, 0.5 * ms], ["wait", 0.5 * ms, 9 * ms],
                 ["fetch_metrics", 9.5 * ms, 0.5 * ms]],
    }


def test_op_parts():
    parts = P.op_parts(HLO)
    assert parts == {"fusion.1": "score_select/eq6_probe",
                     "reshape.2": "score_select/eq6_probe",
                     "select_topk.3": "score_select/eq79_select",
                     "fusion.4": "score_select/telemetry",
                     "fusion.7": "update_context/telemetry"}
    # the stage scopes read as before
    assert T.op_names(HLO)["reshape.2"] == ("score_select", None)


def test_part_seconds():
    ev = events()
    parts = P.part_seconds(ev, P.op_parts(HLO), M)
    assert parts == pytest.approx({"score_select/eq6_probe": 0.003,
                                   "score_select/eq79_select": 0.0005,
                                   "score_select/telemetry": 0.00025,
                                   "update_context/telemetry": 0.0005})
    stages = T.reduce(ev, T.op_names(HLO), M)["stage_s"]
    assert stages["score_select"] == pytest.approx(0.004)
    score = sum(v for k, v in parts.items() if k.startswith("score_select/"))
    assert score <= stages["score_select"]


def test_tiny_chunk_carries_stages_and_parts():
    """The compiled chunk of the tiny pfeddst cell names every stage, and
    the three parts of score_select, in its op_name metadata."""
    prog = harness.Program(bench_paths.tiny_cell("pfeddst.m16"))
    prog.first_chunk(11)
    hlo = prog.compiled.as_text()
    assert STAGES <= {s for s, _ in T.op_names(hlo).values() if s}
    found = set(P.op_parts(hlo).values())
    assert SCORE_PARTS <= found
    assert found <= SCORE_PARTS | {"update_context/telemetry"}


def load():
    with open(os.path.join(HERE, "trace_pfeddst_parts.json")) as fh:
        return json.load(fh)


def test_recorded_parts():
    """On the chip, the three parts hold nearly all of score_select's
    device time, and the numbers reduce as they did when recorded."""
    fx = load()
    names = {k: tuple(v) for k, v in fx["names"].items()}
    r = T.reduce(fx["events"], names, fx["module"])
    parts = P.part_seconds(fx["events"], fx["parts"], fx["module"])
    assert SCORE_PARTS <= set(parts)
    score = sum(parts[k] for k in SCORE_PARTS)
    assert 0.9 * r["stage_s"]["score_select"] <= score \
        <= r["stage_s"]["score_select"]
    for k in ("window_s", "busy_s"):
        assert r[k] == pytest.approx(fx["reduced"][k], rel=1e-9)
    assert r["stage_s"] == pytest.approx(fx["reduced"]["stage_s"],
                                         rel=1e-9)
    assert parts == pytest.approx(fx["part_s"], rel=1e-9)


def read(metric, trace, rounds):
    return workload.load_module("metrics", metric).read(
        {"trace": trace, "rounds": rounds})


def test_part_readers():
    """probe_ms and select_ms: a part's device time per round, summed
    over devices and averaged over them; nothing where the trace holds
    no such part."""
    ev = events()
    tr = T.reduce(ev, T.op_names(HLO), M)
    tr["part_s"] = P.part_seconds(ev, P.op_parts(HLO), M)
    assert read("probe_ms", tr, 2) == pytest.approx(1.5)
    assert read("select_ms", tr, 2) == pytest.approx(0.25)
    tr["part_s"] = {"update_context/telemetry": 0.0005}
    assert read("probe_ms", tr, 2) is None
    assert read("select_ms", tr, 2) is None


def test_recorded_parts_feed_the_readers():
    """On the recorded chip trace, the probe and the selection hold
    nearly all of score_select_ms (the telemetry part the rest)."""
    fx = load()
    names = {k: tuple(v) for k, v in fx["names"].items()}
    tr = T.reduce(fx["events"], names, fx["module"])
    tr["part_s"] = P.part_seconds(fx["events"], fx["parts"], fx["module"])
    probe, select, score = (read(m, tr, 2) for m in (
        "probe_ms", "select_ms", "score_select_ms"))
    assert probe == pytest.approx(
        1000 * fx["part_s"]["score_select/eq6_probe"] / 2, rel=1e-9)
    assert select == pytest.approx(
        1000 * fx["part_s"]["score_select/eq79_select"] / 2, rel=1e-9)
    assert 0 < select < probe
    assert 0.9 * score <= probe + select <= score
