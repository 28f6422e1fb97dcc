"""repro.obs — registry, trace schema, timers, selection probe, and the
observability wiring (simulator trace golden, History schema vs docs,
select_topk auto-routing, bench_diff / trace_report tools)."""
import importlib.util
import json
import os
import re
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.scoring import score_topk, selected_components
from repro.core.selection import NEG, as_cost_matrix
from repro.fl.simulator import History
from repro.kernels.ops import resolve_select_impl, select_topk
from repro.obs import (
    DEFAULT_REGISTRY,
    CompileLog,
    MetricRegistry,
    RoundClock,
    SelectionGraph,
    StageTimes,
    check_fused_parity,
    components_of_selected,
    decompose_scores,
    header_record,
    instrument_stages,
    probe_topk,
    read_trace,
    round_record,
    scalar_metrics,
    score_block,
    stage_name,
    stage_profile_record,
    summary_record,
    validate_record,
    validate_trace,
)
from repro.obs.trace import TraceWriter

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GOLDEN = os.path.join(REPO, "tests", "golden")


def _load_module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_catalog_and_stub():
    reg = MetricRegistry()
    reg.register("my_metric", stage="stage_x", doc="a doc")
    assert "my_metric" in reg
    assert reg.describe("my_metric").stage == "stage_x"
    # unregistered names are first-class: describe returns a stub
    stub = reg.describe("never_registered")
    assert stub.name == "never_registered"
    assert "unregistered" in stub.doc
    with pytest.raises(ValueError):
        reg.register("bad", kind="tensor")


def test_default_registry_documents_builtin_metrics():
    for name in ("train_loss_e", "mean_selected_score", "sel_s_l_mean",
                 "sel_s_d_mean", "sel_s_p_mean", "sel_cost_mean",
                 "s_l_mean", "s_d_offdiag_mean"):
        assert name in DEFAULT_REGISTRY, name
        assert DEFAULT_REGISTRY.describe(name).kind == "scalar"
    for name in ("active", "stale", "select_mask"):
        assert DEFAULT_REGISTRY.describe(name).kind == "array"


def test_scalar_metrics_picks_only_scalars():
    metrics = {
        "loss": jnp.asarray(1.5),
        "active": jnp.ones((4,), bool),
        "count": np.int64(3),
        "mask": np.zeros((2, 2)),
    }
    out = scalar_metrics(metrics)
    assert out == {"loss": 1.5, "count": 3.0}
    assert all(isinstance(v, float) for v in out.values())


# ---------------------------------------------------------------------------
# trace schema
# ---------------------------------------------------------------------------

def _valid_round(rnd=0, **kw):
    base = dict(
        rnd=rnd, wall_s=0.1, compile_round=(rnd == 0), active=4,
        stale_mean=0.0, stale_max=0,
        comm={"bytes": 10, "net_time_s": 0.1, "energy_j": 0.2},
        device={"wall_s": 0.0, "straggler_s": 0.0, "eff_lag": 0.0},
        metrics={"train_loss": 1.0},
    )
    base.update(kw)
    return round_record(**base)


def test_trace_writer_roundtrip(tmp_path):
    path = str(tmp_path / "t.jsonl")
    with TraceWriter(path) as tw:
        tw.write(header_record(strategy="pfeddst", num_clients=8,
                               num_rounds=2, seed=0))
        tw.write(_valid_round(0))
        tw.write(_valid_round(
            1, metrics={"train_loss": jnp.asarray(0.5)},
            eval_point={"accuracy": 0.5, "train_loss": 0.5},
        ))
        tw.write(summary_record(rounds=2, wall_s=0.2, compile_s=1.0))
    records, errors = validate_trace(path)
    assert errors == []
    assert [r["type"] for r in records] == \
        ["header", "round", "round", "summary"]
    # jax scalar became a plain JSON number
    assert records[2]["metrics"]["train_loss"] == 0.5
    assert read_trace(path) == records


def test_trace_writer_rejects_invalid():
    import io

    tw = TraceWriter.__new__(TraceWriter)
    tw._fh, tw.records = io.StringIO(), 0
    with pytest.raises(ValueError):
        tw.write({"type": "round", "round": 0})    # missing required keys
    with pytest.raises(ValueError):
        tw.write({"type": "nonsense"})


def test_validate_record_checks_sub_blocks():
    rec = _valid_round(0)
    del rec["comm"]["energy_j"]
    assert any("energy_j" in e for e in validate_record(rec))
    rec = _valid_round(0, score={"s_l": 1.0})      # incomplete score block
    assert any("score" in e for e in validate_record(rec))
    rec = _valid_round(0, metrics={"arr": [1, 2]})  # non-scalar metric
    assert any("non-scalar" in e for e in validate_record(rec))
    bad_hdr = header_record(strategy="s", num_clients=1, num_rounds=1)
    bad_hdr["schema"] = 99
    assert any("schema" in e for e in validate_record(bad_hdr))


def test_validate_trace_file_level(tmp_path):
    path = str(tmp_path / "bad.jsonl")
    with open(path, "w") as fh:
        fh.write(json.dumps(_valid_round(1)) + "\n")    # no header
        fh.write(json.dumps(_valid_round(0)) + "\n")    # decreasing round
    _, errors = validate_trace(path)
    assert any("header" in e for e in errors)
    assert any("increasing" in e for e in errors)
    assert validate_trace(str(tmp_path / "nothing"))[1] \
        if os.path.exists(str(tmp_path / "nothing")) else True


def test_traffic_stats_comm_block_matches_trace_schema():
    from repro.comms.transport import TrafficStats
    from repro.obs.trace import COMM_KEYS

    block = TrafficStats.zero(4).to_comm_block()
    assert set(block) == set(COMM_KEYS)
    rec = _valid_round(0, comm=block)
    assert validate_record(rec) == []


def test_score_block_requires_all_components():
    metrics = {"sel_s_l_mean": 1.0, "sel_s_d_mean": 0.1,
               "sel_s_p_mean": 0.9, "sel_cost_mean": 1.0,
               "mean_selected_score": 2.0}
    block = score_block(metrics)
    assert block == {"s_l": 1.0, "s_d": 0.1, "s_p": 0.9,
                     "cost": 1.0, "total": 2.0}
    del metrics["sel_cost_mean"]
    assert score_block(metrics) is None


# ---------------------------------------------------------------------------
# timers
# ---------------------------------------------------------------------------

def test_stage_times_first_steady_split():
    times = StageTimes()
    times.add("s", 1.0)
    times.add("s", 0.2)
    times.add("s", 0.4)
    s = times.summary()["s"]
    assert s["first_s"] == 1.0
    assert s["steady_s"] == pytest.approx(0.3)
    assert s["compile_s"] == pytest.approx(0.7)
    assert s["calls"] == 3
    # single call: steady 0, compile = first
    times.add("once", 0.5)
    once = times.summary()["once"]
    assert once["steady_s"] == 0.0 and once["compile_s"] == 0.5


def test_instrument_stages_times_and_names():
    def alpha(state, ctx):
        return state + 1

    def beta(state, ctx):
        ctx.metrics["x"] = jnp.asarray(1.0)
        return state

    beta.stage_name = "custom_beta"
    times = StageTimes()
    wrapped = instrument_stages((alpha, beta), times)
    assert [stage_name(s) for s in wrapped] == ["alpha", "custom_beta"]
    ctx = SimpleNamespace(metrics={}, aux={})
    state = jnp.asarray(0)
    for _ in range(2):
        for stage in wrapped:
            state = stage(state, ctx)
    assert int(state) == 2
    summary = times.summary()
    assert set(summary) == {"alpha", "custom_beta"}
    assert all(s["calls"] == 2 for s in summary.values())


PFEDDST_STAGES = ("score_select", "aggregate", "phase_e", "phase_h",
                  "update_context")


@pytest.mark.parametrize("name,stages", [
    ("pfeddst", PFEDDST_STAGES),
    ("pfeddst_async", ("deadline_gate", *PFEDDST_STAGES[:4], "publish",
                       PFEDDST_STAGES[4])),
])
def test_pfeddst_stage_names(name, stages):
    """The stage scopes' names are fixed by the stages, not by the
    closures' __name__s: device profiles read them."""
    from repro.configs import get_config
    from repro.configs.base import FLConfig
    from repro.fl.strategies import make_spec

    cfg = get_config("resnet18-cifar").reduced()
    spec = make_spec(name, cfg, FLConfig(num_clients=4, peers_per_round=1),
                     steps_per_epoch=1)
    assert tuple(stage_name(s) for s in spec.stages) == stages
    assert all(s.stage_name == stage_name(s) for s in spec.stages)


def test_compile_log_takes_unions_of_nested_traces():
    """A jit that calls a nested jit traces the inner one inside its own
    trace: the log's trace_s is the union, no longer than the wall of
    lowering and compiling, and the compile is counted."""
    @jax.jit
    def inner(x):
        return jnp.sin(x) * 2

    @jax.jit
    def outer(x):
        return inner(x) + inner(x + 1)

    x = jnp.arange(4.0)
    with CompileLog() as log:
        t0 = time.perf_counter()
        outer.lower(x).compile()
        wall = time.perf_counter() - t0
    s = log.summary()
    assert 0 < s["trace_s"] <= wall
    assert 0 < s["compile_s"] <= wall
    assert s["compiles"] >= 1 and s["cache_hits"] == 0
    # closed: later compiles are not heard
    jax.jit(lambda v: v - 1).lower(x).compile()
    assert log.summary() == s


def test_round_clock_compile_steady_split():
    clock = RoundClock()
    with clock.round():
        time.sleep(0.02)
    with clock.round():
        pass
    with clock.round():
        pass
    assert clock.rounds == 3
    assert clock.compile_s >= 0.02
    assert clock.elapsed() == clock.steady_s < clock.compile_s
    assert clock.last_s <= clock.steady_s


# ---------------------------------------------------------------------------
# selection probe
# ---------------------------------------------------------------------------

def _probe_inputs(m=12, p=16, seed=0):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    headers = jax.random.normal(k1, (m, p))
    last = jnp.where(
        jax.random.uniform(k2, (m, m)) < 0.5,
        jax.random.randint(k2, (m, m), 0, 4), -1,
    ).astype(jnp.int32)
    loss = jax.random.uniform(k3, (m, m))
    return headers, last, loss


@pytest.mark.parametrize("cost", [0.3, "matrix"])
def test_probe_matches_fused_pipeline(cost):
    headers, last, loss = _probe_inputs()
    m = headers.shape[0]
    if cost == "matrix":
        cost = jnp.abs(jax.random.normal(jax.random.PRNGKey(9), (m, m)))
    kw = dict(alpha=1.0, lam=0.5, comm_cost=cost)
    vals, idx, _ = score_topk(headers, last, loss, jnp.asarray(5.0),
                              k=3, impl="blocked", **kw)
    dec = decompose_scores(headers, last, loss, jnp.asarray(5.0), **kw)
    check_fused_parity(dec, vals, idx)        # raises on mismatch
    pv, pi = probe_topk(dec, 3)
    np.testing.assert_array_equal(np.asarray(pi), np.asarray(idx))
    # gathered components recombine to the kernel's scores, and agree
    # with the always-on O(M·k) selected_components path
    comp = components_of_selected(dec, idx, alpha=1.0)
    np.testing.assert_allclose(np.asarray(comp["score"]),
                               np.asarray(vals), atol=1e-5)
    sel = selected_components(headers, last, loss, jnp.asarray(5.0), idx,
                              alpha=1.0, lam=0.5, comm_cost=cost)
    for name in ("s_l", "s_d", "s_p", "cost"):
        np.testing.assert_allclose(np.asarray(comp[name]),
                                   np.asarray(sel[name]), atol=1e-5)


def test_recording_branches_agree_fused_vs_dense():
    """The two `score_select` recording branches (fused: gathered (M, k)
    components; dense: masked (M, M) reductions) must emit the same
    sel_*_mean values."""
    headers, last, loss = _probe_inputs(seed=3)
    m = headers.shape[0]
    t, alpha, lam, cost, k = jnp.asarray(4.0), 1.0, 0.5, 0.25, 3
    vals, idx, _ = score_topk(headers, last, loss, t, k=k, impl="blocked",
                              alpha=alpha, lam=lam, comm_cost=cost)
    active = jnp.arange(m) % 2 == 0
    from repro.core.selection import topk_to_mask

    mask = topk_to_mask(idx, vals, m) & active[:, None]
    n_sel = jnp.maximum(jnp.sum(mask), 1).astype(jnp.float32)
    comp = selected_components(headers, last, loss, t, idx,
                               alpha=alpha, lam=lam, comm_cost=cost)
    valid = (vals > NEG / 2) & active[:, None]
    dec = decompose_scores(headers, last, loss, t,
                           alpha=alpha, lam=lam, comm_cost=cost)
    dense_mats = {"s_l": loss, "s_d": dec["s_d"], "s_p": dec["s_p"],
                  "cost": as_cost_matrix(cost, m)}
    for name in ("s_l", "s_d", "s_p", "cost"):
        fused_mean = jnp.sum(jnp.where(valid, comp[name], 0.0)) / n_sel
        dense_mean = jnp.sum(jnp.where(mask, dense_mats[name], 0.0)) / n_sel
        np.testing.assert_allclose(float(fused_mean), float(dense_mean),
                                   atol=1e-5)


def test_selection_graph_counts_churn_and_export(tmp_path):
    g = SelectionGraph(4)
    mask = np.zeros((4, 4), bool)
    mask[0, 1] = mask[2, 3] = True
    g.observe(mask)
    assert g.churn == [0.0]
    g.observe(np.asarray([[0, 1], [1, 2]]))   # edge-array form
    assert g.rounds == 2
    # Jaccard churn: share {0,1}; union 3 → 1 - 1/3
    assert g.churn[1] == pytest.approx(2 / 3)
    assert g.counts[0, 1] == 2 and g.counts[2, 3] == 1
    edges = g.edge_list()
    assert edges[0] == [0, 1, 2]              # sorted by count desc
    assert g.frequency()[0, 1] == 1.0
    rec = g.to_record()
    assert validate_record(rec) == []
    out = str(tmp_path / "graph.json")
    g.export_json(out)
    with open(out) as fh:
        assert json.load(fh) == rec


# ---------------------------------------------------------------------------
# select_topk auto-routing (satellite regression)
# ---------------------------------------------------------------------------

def test_resolve_select_impl_threshold_table():
    # CPU: dense below 2048 (BENCH_select.json shows blocked LOSING
    # 0.72–0.88x at M<=1024), blocked at and above
    assert resolve_select_impl(16, "cpu") == "dense"
    assert resolve_select_impl(1024, "cpu") == "dense"
    assert resolve_select_impl(2048, "cpu") == "blocked"
    assert resolve_select_impl(4096, "cpu") == "blocked"
    assert resolve_select_impl(512, "gpu") == "dense"
    assert resolve_select_impl(1024, "gpu") == "blocked"
    # TPU always takes the fused Pallas kernel
    for m in (16, 4096):
        assert resolve_select_impl(m, "tpu") == "pallas"
    # unknown backends get the conservative CPU threshold
    assert resolve_select_impl(1024, "rocm") == "dense"
    # default backend (cpu in this container) routes small M to dense
    assert resolve_select_impl(64) == resolve_select_impl(
        64, jax.default_backend()
    )


def test_select_topk_impls_agree_and_auto_routes():
    headers, last, loss = _probe_inputs(m=16)
    kw = dict(k=3, alpha=1.0, lam=0.5)
    outs = {
        impl: select_topk(headers, last, loss, jnp.asarray(3.0),
                          jnp.asarray(0.1), impl=impl, **kw)
        for impl in ("dense", "blocked", "pallas", "auto")
    }
    ref_v, ref_i, ref_s = outs["dense"]
    for impl in ("blocked", "pallas", "auto"):
        v, i, s = outs[impl]
        np.testing.assert_array_equal(np.asarray(i), np.asarray(ref_i))
        np.testing.assert_allclose(np.asarray(v), np.asarray(ref_v),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(s), np.asarray(ref_s),
                                   atol=1e-4)
    with pytest.raises(ValueError):
        select_topk(headers, last, loss, jnp.asarray(3.0),
                    jnp.asarray(0.1), impl="nope", **kw)


# ---------------------------------------------------------------------------
# History schema (satellite)
# ---------------------------------------------------------------------------

def _history_fields():
    import dataclasses

    return {f.name for f in dataclasses.fields(History)}


def test_history_to_dict_serializes_every_field():
    hist = History()
    hist.rounds, hist.accuracy = [2], [0.5]
    hist.train_loss, hist.wall_s = [1.0], [0.1]
    hist.compile_s = 3.0
    hist.extra = {"sel_s_l_mean": [jnp.asarray(1.5)]}
    d = hist.to_dict()
    assert set(d) == _history_fields()
    # JSON round-trip: everything must already be plain Python
    assert json.loads(json.dumps(d)) == d
    assert d["extra"]["sel_s_l_mean"] == [1.5]
    assert d["compile_s"] == 3.0


def test_history_schema_matches_architecture_docs():
    """Every History field appears (backticked, first column) in the
    History-schema tables of docs/architecture.md — and nothing extra."""
    doc = open(os.path.join(REPO, "docs", "architecture.md")).read()
    section = doc.split("## History schema", 1)[1]
    documented = set()
    for line in section.splitlines():
        if line.startswith("|") and "`" in line:
            first_cell = line.split("|")[1]
            documented |= set(re.findall(r"`([A-Za-z_][A-Za-z_0-9]*)`",
                                         first_cell))
    documented.discard("field")
    assert documented == _history_fields()


# ---------------------------------------------------------------------------
# tools
# ---------------------------------------------------------------------------

def test_bench_diff_flags_regressions(tmp_path):
    bd = _load_module(os.path.join(REPO, "tools", "bench_diff.py"),
                      "bench_diff")
    old = {"rounds": {"pfeddst": {"M16": {
        "steady_s": 1.0, "compile_s": 5.0, "first_s": 6.0, "calls": 3}}}}
    new = json.loads(json.dumps(old))
    new["rounds"]["pfeddst"]["M16"]["steady_s"] = 1.10   # +10% — under gate
    _, regressions = bd.diff(old, new, threshold=0.15)
    assert regressions == []
    new["rounds"]["pfeddst"]["M16"]["steady_s"] = 1.30   # +30% — flagged
    _, regressions = bd.diff(old, new, threshold=0.15)
    assert len(regressions) == 1 and "steady_s" in regressions[0]
    # compile noise tolerated up to 2x, flagged beyond
    new["rounds"]["pfeddst"]["M16"] = {"steady_s": 1.0, "compile_s": 9.0,
                                       "first_s": 6.0, "calls": 3}
    _, regressions = bd.diff(old, new, threshold=0.15)
    assert regressions == []
    new["rounds"]["pfeddst"]["M16"]["compile_s"] = 11.0
    _, regressions = bd.diff(old, new, threshold=0.15)
    assert len(regressions) == 1
    # exit codes through main()
    po, pn = str(tmp_path / "o.json"), str(tmp_path / "n.json")
    json.dump(old, open(po, "w"))
    json.dump(new, open(pn, "w"))
    assert bd.main([po, pn]) == 1
    assert bd.main([po, po]) == 0


def test_bench_diff_total_wall_gate(tmp_path):
    """Compile+steady are gated TOGETHER through the synthetic
    total_wall_s leaf: a compile blow-up under the loose 2x per-leaf
    gate still flags once the 10-round total regresses >15%."""
    bd = _load_module(os.path.join(REPO, "tools", "bench_diff.py"),
                      "bench_diff")
    old = {"rounds": {"pfeddst": {"M16": {
        "steady_s": 1.0, "compile_s": 5.0, "first_s": 6.0, "calls": 3}}}}
    bd.add_total_wall(old)
    assert old["rounds"]["pfeddst"]["M16"]["total_wall_s"] == 15.0
    # compile/first both stay under their own 2x gates (5 -> 9.9,
    # 6 -> 10.9), but the synthetic total (15 -> 19.9, +33%) is held
    # to the normal threshold
    new = {"rounds": {"pfeddst": {"M16": {
        "steady_s": 1.0, "compile_s": 9.9, "first_s": 10.9, "calls": 3}}}}
    bd.add_total_wall(new)
    _, regressions = bd.diff(old, new, threshold=0.15)
    assert len(regressions) == 1 and "total_wall_s" in regressions[0]
    # scan entries carry a MEASURED total_s and are left alone
    scan = {"scan": {"first_s": 5.0, "total_s": 6.0, "rounds": 10,
                     "chunk_rounds": 10, "speedup": 2.5}}
    bd.add_total_wall(scan)
    assert "total_wall_s" not in scan["scan"]
    # end-to-end: main() exits 1 on the total-wall regression
    po, pn = str(tmp_path / "o.json"), str(tmp_path / "n.json")
    json.dump({"rounds": {"x": {"first_s": 6.0, "steady_s": 1.0}}},
              open(po, "w"))
    json.dump({"rounds": {"x": {"first_s": 11.9, "steady_s": 1.0}}},
              open(pn, "w"))
    assert bd.main([po, pn]) == 1


def test_trace_report_renders_and_validates(tmp_path):
    tr = _load_module(os.path.join(REPO, "tools", "trace_report.py"),
                      "trace_report")
    path = str(tmp_path / "t.jsonl")
    with TraceWriter(path) as tw:
        tw.write(header_record(strategy="pfeddst", num_clients=4,
                               num_rounds=2))
        tw.write(stage_profile_record(
            {"phase_e": {"first_s": 1.0, "steady_s": 0.5,
                         "compile_s": 0.5, "calls": 2}}))
        for r in range(2):
            tw.write(_valid_round(
                r,
                score={"s_l": 1.0, "s_d": 0.1, "s_p": 1.0, "cost": 1.0,
                       "total": 1.9},
                eval_point={"accuracy": 0.25, "train_loss": 2.0},
            ))
        g = SelectionGraph(4)
        g.observe(np.asarray([[0, 1]]))
        tw.write(g.to_record())
        tw.write(summary_record(
            rounds=2, wall_s=0.2, compile_s=1.0,
            compile_log={"trace_s": 0.5, "compile_s": 0.8, "compiles": 3,
                         "cache_hits": 1}))
    assert tr.main([path, "--validate"]) == 0
    text = tr.report(read_trace(path))
    for token in ("strategy=pfeddst", "phase_e", "Eq. 9", "selection graph",
                  "summary", "3 compiled, 1 from the cache"):
        assert token in text, token
    # schema violations -> nonzero exit under --validate
    with open(path, "a") as fh:
        fh.write(json.dumps({"type": "round", "round": 5}) + "\n")
    assert tr.main([path, "--validate"]) == 1


# ---------------------------------------------------------------------------
# traced simulator run vs the golden trace (slow tier)
# ---------------------------------------------------------------------------

HOST_TIME_KEYS = {"wall_s", "compile_s", "first_s", "steady_s",
                  "compile_log"}


def _strip_host_time(obj):
    if isinstance(obj, dict):
        return {k: _strip_host_time(v) for k, v in obj.items()
                if k not in HOST_TIME_KEYS}
    if isinstance(obj, list):
        return [_strip_host_time(v) for v in obj]
    return obj


def _assert_close_tree(a, b, path=""):
    assert type(a) is type(b), f"{path}: {type(a)} vs {type(b)}"
    if isinstance(a, dict):
        assert set(a) == set(b), f"{path}: keys {set(a) ^ set(b)}"
        for k in a:
            _assert_close_tree(a[k], b[k], f"{path}.{k}")
    elif isinstance(a, list):
        assert len(a) == len(b), f"{path}: len {len(a)} vs {len(b)}"
        for i, (x, y) in enumerate(zip(a, b)):
            _assert_close_tree(x, y, f"{path}[{i}]")
    elif isinstance(a, float):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=1e-3,
                                   err_msg=path)
    else:
        assert a == b, f"{path}: {a!r} != {b!r}"


@pytest.mark.slow
def test_traced_sim_reproduces_golden_trace(tmp_path):
    mg = _load_module(os.path.join(GOLDEN, "make_goldens.py"),
                      "make_goldens")
    path = mg.make_trace(str(tmp_path / "trace.jsonl"))
    records, errors = validate_trace(path)
    assert errors == []
    golden = read_trace(os.path.join(GOLDEN, "trace_pfeddst.jsonl"))
    assert [r["type"] for r in records] == [g["type"] for g in golden]
    # host wall times vary run-to-run; everything else is fixed-seed
    # deterministic and held to the engine-parity tolerance
    _assert_close_tree(_strip_host_time(records), _strip_host_time(golden))
    # and the trace carries the observability payload the issue demands:
    rounds = [r for r in records if r["type"] == "round"]
    assert rounds[0]["compile"] and not rounds[1]["compile"]
    assert all(r["score"] is not None and "s_l" in r["score"]
               for r in rounds)
    assert all(r["edges"] for r in rounds)
    assert any("eval" in r for r in rounds)


@pytest.mark.slow
def test_traced_sim_fills_history_extra(tmp_path):
    mg = _load_module(os.path.join(GOLDEN, "make_goldens.py"),
                      "make_goldens")
    from repro.fl import run_experiment

    cfg, fl, data = mg.trace_config()
    hist = run_experiment(
        "pfeddst", cfg, fl, data, num_rounds=2, eval_every=2,
        steps_per_epoch=1, seed=0, verbose=False,
    )
    for name in ("sel_s_l_mean", "sel_s_d_mean", "sel_s_p_mean",
                 "sel_cost_mean", "mean_selected_score"):
        assert name in hist.extra, name
        assert len(hist.extra[name]) == 2
    assert hist.compile_s > 0
    assert hist.wall_s[-1] < hist.compile_s  # steady wall excludes compile
