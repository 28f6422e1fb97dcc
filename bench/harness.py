"""One run of one cell: set-up, warm-up, the measured window, the trace,
and the comparison with the plain reference.

The system under test is the chunk program of `repro.fl.engine.
make_multi_round`: C rounds of the cell's strategy in one jitted scan,
driven as `run_experiment` drives it (each chunk ends in
`block_until_ready` and a `device_get` of its stacked metrics). Set-up
builds that one compiled program and the population state, and runs the
first chunk from the seed; that chunk is what the reference checks. The
window then runs whole chunks on the same state until its seconds have
passed.

The cell's model (bench/models/<model>.py, named by the configuration's
`model`) sizes the program's config and makes the data and weights; this
file knows nothing of any one model.
"""
from __future__ import annotations

import contextlib
import functools
import importlib
import math
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp

import parts
import population
import tracing
import workload

TRACE_DIR = os.path.join(workload.ROOT, ".bench_trace")
GIB = 2.0 ** 30
NOT_FINITE = 1e300      # a compared number that is not finite, in JSON


def log(*parts):
    print(*parts, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# the system under test, built from the cell's files
# ---------------------------------------------------------------------------

def fl_config(s: dict):
    """The program's FLConfig for the merged sizes of a cell."""
    import dataclasses

    from repro.configs.base import CommsConfig, FLConfig

    names = {f.name for f in dataclasses.fields(FLConfig)}
    kw = {k: v for k, v in s.items() if k in names}
    return FLConfig(comms=CommsConfig(topology=s["topology"]), **kw)


def build(sizes: dict, name: str, model):
    """The program's config (the registry's `name`, sized by the model's
    `program_config`), FL config and strategy."""
    from repro.configs import get_config
    from repro.fl.strategies import make_strategy

    cfg = model.program_config(get_config(name), sizes)
    fl = fl_config(sizes)
    return cfg, fl, make_strategy(sizes["strategy"], cfg, fl,
                                  sizes["steps_per_epoch"])


def weights_fn(sizes: dict, model):
    """jitted keys → {"params", "masks", "shared"}: the benchmark's
    initial weights of every client, DisPFL's masks, and the weights that
    every client shares (None where the model has none), on the device."""
    shared = workload.has_shared(model)

    @jax.jit
    def make(k_w, k_m):
        params = model.init_population(sizes, k_w)
        masks = population.init_masks(params, sizes["dispfl_sparsity"], k_m) \
            if sizes["strategy"] == "dispfl" else None
        return {"params": params, "masks": masks,
                "shared": model.init_shared(
                    sizes, population.shared_key(k_w)) if shared else None}

    return make


class CompileCounter:
    """Counts the programs XLA compiles or loads while it is open."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        self.n = 0
        self.on = False
        jax.monitoring.register_event_duration_secs_listener(self._hear)

    def _hear(self, event, duration, **kw):
        if self.on and event in self.EVENTS:
            self.n += 1


# ---------------------------------------------------------------------------
# a run
# ---------------------------------------------------------------------------

class Program:
    """The system under test for one cell: the strategy, its compiled
    chunk program (built on first use), and the benchmark's inputs."""

    def __init__(self, cell: dict, program_hook=None):
        self.cell, self.sizes = cell, cell["sizes"]
        self.chunk = self.sizes["chunk_rounds"]
        self.model = workload.model(cell)
        self.ref = importlib.import_module(
            f"reference.{self.sizes['strategy']}")
        if program_hook is not None:
            program_hook()
        self.cfg, self.fl, self.strat = build(self.sizes, cell["model"],
                                              self.model)
        self.make_w = weights_fn(self.sizes, self.model)
        self.compiled = None
        if workload.has_shared(self.model):
            # traced, not compiled: a program whose state has no place for
            # the shared weights stops here, before any compile
            jax.eval_shape(lambda k: self.initial_state(k, self.make_w(k, k)),
                           jax.random.PRNGKey(0))

    def initial_state(self, k_w, weights):
        """The program's initial state around the benchmark's weights."""
        return self.ref.program_state(self.strat, k_w, weights,
                                      model=self.model)

    def first_chunk(self, seed: int):
        """Inputs, weights and state from the seed, then the first chunk
        through the compiled program → (state, data, keys)."""
        from repro.fl.engine import make_multi_round, place_population, \
            population_mesh

        keys = population.seed_keys(seed)
        data = self.model.make_train_data(self.sizes, keys["data"])
        state = jax.jit(self.initial_state)(
            keys["weights"], self.make_w(keys["weights"], keys["masks"]))
        # no mesh on one chip; on four, the shared weights (no client
        # axis) are to be replicated, which place_population does not do
        # for a leaf whose first axis happens to be M: left to the change
        # that runs such a model on four chips
        self.mesh = population_mesh()
        if self.mesh is not None:
            state = place_population(state, self.fl.num_clients, self.mesh)
            data = place_population(data, self.fl.num_clients, self.mesh)
        with self.mesh_ctx():
            if self.compiled is None:
                fn = make_multi_round(self.strat.spec, self.fl,
                                      self.strat.fabric,
                                      chunk_rounds=self.chunk)
                self.compiled = fn.lower(state, data, keys["rounds"],
                                         jnp.int32(0)).compile()
            state, stacked = self.compiled(state, data, keys["rounds"],
                                           jnp.int32(0))
            jax.block_until_ready((state, stacked))
        return state, jax.device_get(stacked), data, keys

    def mesh_ctx(self):
        return jax.set_mesh(self.mesh) if self.mesh is not None \
            else contextlib.nullcontext()

    def init(self, keys):
        """A zero-argument maker of the seed's initial weights."""
        return functools.partial(self.make_w, keys["weights"], keys["masks"])

    def snapshot(self, state, stacked, keys):
        w0 = self.init(keys)()
        return self.ref.snapshot_program(state, stacked, w0["params"],
                                         self.model, w0["shared"])

    def reference(self, data, keys, quant=None):
        return self.ref.run_reference(self.sizes, data, self.init(keys),
                                      keys["rounds"], self.chunk, self.model,
                                      quant)


def enable_cache():
    """JAX's persistent compilation cache at its fixed path in the
    checkout (or $JAX_COMPILATION_CACHE_DIR), every program in it, so a
    warm set-up compiles nothing."""
    from repro.utils.compile_cache import enable_compilation_cache

    return enable_compilation_cache(min_compile_secs=0.0)


def run(cell: dict, *, seed: int, seconds: float, trace: bool, t0: float,
        devices, peak: dict, program_hook=None) -> dict:
    """→ the result line. `program_hook()` may replace parts of the
    program before it is built (the fault tests)."""
    prog_ = Program(cell, program_hook)
    counter = CompileCounter()

    # ---- set-up: inputs, weights, state, the compiled chunk, warm-up ------
    state, stacked, data, keys = prog_.first_chunk(seed)
    setup_s = time.perf_counter() - t0
    # what the reference will be held against (not set-up)
    prog = prog_.snapshot(state, stacked, keys)
    compiled, chunk = prog_.compiled, prog_.chunk

    # ---- the measured window ----------------------------------------------
    with prog_.mesh_ctx():
        if trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            jax.profiler.start_trace(TRACE_DIR)
        counter.on = True
        losses, start = [], chunk
        w0 = time.perf_counter()
        while True:
            with jax.profiler.TraceAnnotation("dispatch"):
                state, stacked = compiled(state, data, keys["rounds"],
                                          jnp.int32(start))
            with jax.profiler.TraceAnnotation("wait"):
                jax.block_until_ready((state, stacked))
            with jax.profiler.TraceAnnotation("fetch_metrics"):
                stacked = jax.device_get(stacked)
            losses += _round_losses(stacked)
            start += chunk
            if time.perf_counter() - w0 >= seconds:
                break
        window_s = time.perf_counter() - w0
        counter.on = False
        if trace:
            jax.profiler.stop_trace()
    rounds = start - chunk
    peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for d in devices)
    del state, stacked

    # ---- per-layer metrics from the trace ----------------------------------
    metrics = {}
    breakdown = device_extra = None
    if trace:
        hlo = compiled.as_text()
        events, module = tracing.extract(TRACE_DIR), tracing.module_name(hlo)
        tr = tracing.reduce(events, tracing.op_names(hlo), module)
        tr["part_s"] = parts.part_seconds(events, parts.op_parts(hlo), module)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        info = {"sizes": cell["sizes"], "model": prog_.model,
                "rounds": rounds, "window_s": window_s, "trace": tr,
                "peak": peak, "chips": len(devices)}
        for m in cell["per_layer"]:
            value = workload.load_module("metrics", m["name"],
                                         cell["bench"]).read(info)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        breakdown = {"device_ops": tr["device_ops"],
                     "idle_gaps": tr["idle_gaps"]}
        device_extra = {"busy_s": tr["busy_s"], "window_s": tr["window_s"]}
    else:
        e2e = {"round_s": window_s / rounds, "setup_s": setup_s,
               "peak_hbm_gib": peak_bytes / GIB}
        for m in cell["end_to_end"]:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    log(f"window: {rounds} rounds in {window_s:.3f} s, setup {setup_s:.3f} s, "
        f"peak {peak_bytes} bytes, {counter.n} compiles")

    # ---- the comparison with the reference ---------------------------------
    prog_.compiled = compiled = None
    numbers = prog_.ref.compare(prog, prog_.reference(data, keys))
    numbers["window_compiles"] = float(counter.n)
    numbers = {k: numbers[k] for k in cell["limits"]}
    failed = sum(1 for x in losses if not math.isfinite(x))
    checks = {k: {"value": v if math.isfinite(v) else NOT_FINITE,
                  "limit": cell["limits"][k]}
              for k, v in numbers.items()}
    correct = failed == 0 and all(c["value"] <= c["limit"]
                                  for c in checks.values())

    dev = devices[0]
    result = {
        "correct": bool(correct), "attempted": rounds, "failed": failed,
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices), "memory_peak_bytes": peak_bytes,
                   **(device_extra or {})},
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for k, c in checks.items():
        log(f"check {k} {c['value']!r} limit {c['limit']!r}")
    return result


def _round_losses(stacked) -> list:
    """Per round of a chunk, the sum of its training losses (not finite
    when any of them is not)."""
    import numpy as np

    parts = [np.asarray(v, np.float64) for k, v in stacked.items()
             if "loss" in k and getattr(v, "ndim", 0) == 1]
    return [float(x) for x in np.sum(parts, axis=0)]


