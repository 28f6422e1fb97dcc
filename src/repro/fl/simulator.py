"""Population FL simulator — round loop + personalized evaluation.

Reproduces the paper's §III protocol on the synth-CIFAR substrate:
M clients, pathological partition, SGD(0.1, m=0.9, wd=0.005), batch 128,
5 extractor epochs + 1 header epoch per round, 10 peers, 0.1 sampling.

Personalized test accuracy = mean over clients of accuracy of client i's
model on client i's OWN test split (the paper's primary metric).

When the strategy carries a comms fabric (FLConfig.comms, the default),
every round's exchange is priced on the simulated network via
`CommsFabric.account_round` — the engine's round metrics echo the
ExchangePlan (`active`, `comm_edges`/`select_mask`), so the simulator
has no per-strategy accounting branches: History gains per-round bytes
and simulated network time plus cumulative bytes/time/energy at each
eval point. FLConfig(comms=None) restores the paper's costless scalar
world (all comm fields stay zero/empty). Only parameter traffic is
priced; PFedDST's probe/header score context is not (see
repro.comms.transport docstring).
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.comms.transport import payload_bytes_per_client
from repro.configs.base import FLConfig, ModelConfig
from repro.core.partial_freeze import make_phase_steps
from repro.fl.engine import (
    make_multi_round,
    place_population,
    population_mesh,
)
from repro.fl.strategies import Strategy, make_strategy
from repro.models import model as model_mod
from repro.models.split import merge_params, split_params
from repro.obs.registry import scalar_metrics
from repro.obs.timers import (
    CompileLog,
    RoundClock,
    StageTimes,
    instrument_stages,
)
from repro.obs.trace import (
    TraceWriter,
    header_record,
    round_record,
    score_block,
    stage_profile_record,
    summary_record,
)
from repro.optim.sgd import sgd


def _batch_for(cfg: ModelConfig, x, y):
    if cfg.family == "cnn":
        return {"images": x, "labels": y}
    return {"tokens": x}


def evaluate_population(cfg: ModelConfig, params, test_x, test_y):
    """Mean + per-client personalized test accuracy. params: leading-M."""

    def one(p, x, y):
        return model_mod.accuracy(cfg, p, _batch_for(cfg, x, y))

    accs = jax.vmap(one)(params, test_x, test_y)
    return jnp.mean(accs), accs


def _finetune_heads(cfg: ModelConfig, fl: FLConfig, params, train_x, train_y,
                    key, steps: int = 8):
    """FedBABU-style eval-time personalization: fine-tune a throwaway
    header copy on local train data, leave the real state untouched."""
    opt = sgd(fl.lr, momentum=fl.momentum, weight_decay=fl.weight_decay)
    phase = make_phase_steps(cfg, opt)

    def one(p, x, y, k):
        e, h = split_params(cfg, p)
        o = opt.init(h)

        def body(carry, kk):
            h_c, o_c = carry
            idx = jax.random.randint(kk, (fl.batch_size,), 0, x.shape[0])
            batch = _batch_for(cfg, x[idx], y[idx])
            h_c, o_c, _ = phase.phase_h(e, h_c, o_c, batch)
            return (h_c, o_c), None

        (h, _), _ = jax.lax.scan(body, (h, o), jax.random.split(k, steps))
        return merge_params(e, h)

    keys = jax.random.split(key, train_x.shape[0])
    return jax.vmap(one)(params, train_x, train_y, keys)


@dataclass
class History:
    """Experiment trace. Schema documented in docs/architecture.md
    ("History schema"); lengths: per-eval-point lists are appended at
    every eval (every `eval_every` rounds + the last round), per-round
    lists every round.

    `wall_s` is STEADY wall only — cumulative host time spent in rounds
    1.. at each eval point. Round 0's wall (trace + XLA compile + one
    execution) lands in `compile_s` instead, so acc-vs-time curves no
    longer fold the one-off jit tax into the first eval point.
    `extra` is the generic obs channel: every scalar a stage `record`s
    into the round metrics lands here as {name: per-round list}, no
    simulator change needed per metric (repro.obs.registry).
    `state` is the population state after the last round, as placed on
    the devices (the initial state when no round ran); it is not part of
    the schema, and `to_dict` leaves it out."""
    rounds: list = field(default_factory=list)
    accuracy: list = field(default_factory=list)
    train_loss: list = field(default_factory=list)
    wall_s: list = field(default_factory=list)
    compile_s: float = 0.0
    # --- communication budget (repro.comms; zeros when fabric disabled) ----
    round_bytes: list = field(default_factory=list)       # per round
    round_net_time_s: list = field(default_factory=list)  # per round
    # mean lag over the STALE clients only (fresh zeros would dilute the
    # signal toward 0 as p_stale shrinks); 0.0 on rounds with none stale
    round_stale_lag: list = field(default_factory=list)   # per round
    round_stale_max: list = field(default_factory=list)   # per round
    comm_bytes: list = field(default_factory=list)        # cumulative @ eval
    net_time_s: list = field(default_factory=list)        # cumulative @ eval
    energy_j: list = field(default_factory=list)          # cumulative @ eval
    # --- device heterogeneity (repro.fl.hetero; zeros without a profile) ---
    round_device_wall_s: list = field(default_factory=list)     # per round
    round_straggler_wall_s: list = field(default_factory=list)  # per round
    round_eff_lag: list = field(default_factory=list)           # per round
    device_time_s: list = field(default_factory=list)     # cumulative @ eval
    # --- generic recorded-scalar channel (repro.obs) -----------------------
    extra: dict = field(default_factory=dict)             # {name: per round}
    # not a dataclass field: device arrays, outside the serialized schema
    state = None

    def to_dict(self):
        return {
            "rounds": self.rounds,
            "accuracy": [float(a) for a in self.accuracy],
            "train_loss": [float(x) for x in self.train_loss],
            "wall_s": [float(w) for w in self.wall_s],
            "compile_s": float(self.compile_s),
            "round_bytes": [int(b) for b in self.round_bytes],
            "round_net_time_s": [float(t) for t in self.round_net_time_s],
            "round_stale_lag": [float(s) for s in self.round_stale_lag],
            "round_stale_max": [int(s) for s in self.round_stale_max],
            "comm_bytes": [int(b) for b in self.comm_bytes],
            "net_time_s": [float(t) for t in self.net_time_s],
            "energy_j": [float(e) for e in self.energy_j],
            "round_device_wall_s": [
                float(t) for t in self.round_device_wall_s
            ],
            "round_straggler_wall_s": [
                float(t) for t in self.round_straggler_wall_s
            ],
            "round_eff_lag": [float(s) for s in self.round_eff_lag],
            "device_time_s": [float(t) for t in self.device_time_s],
            "extra": {
                name: [float(v) for v in vals]
                for name, vals in self.extra.items()
            },
        }

    def rounds_to_target(self, target: float):
        """First round index reaching `target` accuracy ('-' if never)."""
        for r, a in zip(self.rounds, self.accuracy):
            if a >= target:
                return r
        return None

    def bytes_to_target(self, target: float):
        """Cumulative comm bytes when `target` accuracy is first reached."""
        for a, b in zip(self.accuracy, self.comm_bytes):
            if a >= target:
                return b
        return None


def _stale_summary(stale) -> tuple:
    """(mean lag over stale clients, max lag) — 0s when nobody is stale.

    The mean is over the stale subpopulation only: averaging over all M
    clients dilutes the lag toward 0 with the fresh clients' zeros and
    makes the metric track p_stale instead of the lag distribution.
    """
    if stale is None:
        return 0.0, 0
    arr = np.asarray(stale)
    lagging = arr[arr > 0]
    if lagging.size == 0:
        return 0.0, 0
    return float(lagging.mean()), int(arr.max())


def _profile_stages(strat: Strategy, fl: FLConfig, train_data, key,
                    *, rounds: int = 2) -> dict:
    """Eager per-stage compile/steady profile on THROWAWAY state.

    Runs `rounds` unjitted instrumented rounds (obs.timers) from a fresh
    init so the main (jitted) run's state, PRNG streams, and fabric draws
    are untouched — profiling is a side-channel, never a perturbation.
    """
    from repro.fl.engine import run_round

    times = StageTimes()
    stages = instrument_stages(strat.spec.stages, times)
    k_init, k_rounds = jax.random.split(key)
    state = strat.init(k_init)
    for r in range(rounds):
        aff = (strat.spec.affinity(state)
               if strat.fabric is not None and strat.spec.affinity is not None
               else None)
        state, _ = run_round(
            stages, state, train_data, jax.random.fold_in(k_rounds, r),
            m=fl.num_clients, ratio=fl.client_sample_ratio,
            key_streams=strat.spec.key_streams,
            sample_stream=strat.spec.sample_stream,
            fabric=strat.fabric, affinity=aff,
        )
    return times.summary()


def run_experiment(
    strategy_name: str,
    cfg: ModelConfig,
    fl: FLConfig,
    data: dict,
    *,
    num_rounds: int,
    eval_every: int = 5,
    steps_per_epoch: int = 2,
    seed: int = 0,
    verbose: bool = True,
    trace: str | None = None,
    trace_stages: bool = False,
    trace_edges: bool = False,
    chunk_rounds: int = 1,
    eval_mask=None,
) -> History:
    """data: dict(train_x, train_y, test_x, test_y), leading-M stacked.

    eval_mask: optional (M,) bool — restrict the reported personalized
    accuracy to these clients. The open-world benchmarks pass the honest
    cast (adversary accuracy is not a quantity anyone defends, and churn
    runs only ever field a subset of slots); None keeps the full-M mean
    bitwise identical to the closed-world metric.

    trace: path for a schema-versioned JSONL round trace (repro.obs.trace)
    — one record per round with wall/comm/device blocks, every recorded
    scalar metric, and the Eq. 9 score decomposition when the strategy
    selects; closed with a cumulative selection-graph record + summary.
    trace_stages additionally runs a 2-round eager stage profile on
    throwaway state (see `_profile_stages`); trace_edges embeds per-round
    selected-edge lists in the round records (O(edges) JSON per round).
    With trace=None the run is byte-identical to the untraced path.

    The loop opens host spans on the profiler's host track: `dispatch`,
    `wait` and `fetch_metrics` around each jitted call (chunk or round),
    its fence and the copy of its metrics to the host, `consume` around
    the per-round bookkeeping and trace writing, and `evaluate` (inside
    it) around head fine-tuning and evaluation. Under `jax.profiler.
    trace` they attribute the host time between device work. A
    `CompileLog` is open for the whole run; the trace's summary carries
    it as `compile_log`.

    chunk_rounds > 1 drives CHUNKED execution: `engine.make_multi_round`
    runs up to `chunk_rounds` rounds inside one jit (lax.scan, donated
    population buffers) and the stacked per-round metrics are unstacked
    back into the exact per-round History / JSONL-trace path — records
    stay per-round and schema-valid. Chunks are scheduled to END at
    every eval boundary (so evaluation always sees the state after the
    eval round), which means distinct chunk sizes each compile once.
    The scanned body derives round r's key as fold_in(k_rounds, r) —
    identical to the per-round loop — so fixed-seed results are bitwise
    the same in either mode. `History.compile_s` then covers the first
    CHUNK (one compile + `chunk_rounds` executed rounds); trace records
    of that chunk carry compile_round=True.
    """
    with CompileLog() as compile_log:
        return _run(strategy_name, cfg, fl, data, compile_log,
                    num_rounds=num_rounds, eval_every=eval_every,
                    steps_per_epoch=steps_per_epoch, seed=seed,
                    verbose=verbose, trace=trace, trace_stages=trace_stages,
                    trace_edges=trace_edges, chunk_rounds=chunk_rounds,
                    eval_mask=eval_mask)


def _run(strategy_name, cfg, fl, data, compile_log, *, num_rounds,
         eval_every, steps_per_epoch, seed, verbose, trace, trace_stages,
         trace_edges, chunk_rounds, eval_mask) -> History:
    strat = make_strategy(strategy_name, cfg, fl, steps_per_epoch)
    key = jax.random.PRNGKey(seed)
    k_init, k_rounds, k_ft = jax.random.split(key, 3)
    state = strat.init(k_init)

    train_data = {
        ("images" if cfg.family == "cnn" else "tokens"): data["train_x"],
    }
    if cfg.family == "cnn":
        train_data["labels"] = data["train_y"]
    mesh = population_mesh()
    if mesh is not None:
        state = place_population(state, fl.num_clients, mesh)
        train_data = place_population(train_data, fl.num_clients, mesh)

    # wire size of one message, from the pytree byte counts (utils/pytree)
    payload = 0
    if strat.fabric is not None:
        params0 = strat.params_for_eval(state)
        tree = params0 if strat.payload_kind == "model" \
            else split_params(cfg, params0)[0]
        payload = payload_bytes_per_client(
            tree, fl.num_clients,
            bits=fl.comms.payload_bits,
            overhead_bytes=fl.comms.msg_overhead_bytes,
        )
        payload = int(round(payload * strat.payload_fraction))

    # per-client round wall-times for SYNC strategies under a device
    # profile (semi-async rounds report their own via metrics); the step
    # count is strategy-specific — local_train_steps is the same source
    # the hetero runtime prices pfeddst_async with
    wall_np = None
    if fl.device_profile is not None:
        from repro.fl.hetero import local_wall_times, sample_device_vectors
        from repro.fl.strategies import local_train_steps

        devices = sample_device_vectors(fl.device_profile, fl.num_clients)
        wall_np = local_wall_times(
            devices, local_train_steps(strategy_name, fl, steps_per_epoch),
            fl.device_profile,
        )

    tracer = graph = None
    if trace is not None:
        from repro.obs.selection_probe import SelectionGraph

        tracer = TraceWriter(trace)
        tracer.write(header_record(
            strategy=strategy_name, num_clients=fl.num_clients,
            num_rounds=num_rounds, seed=seed, family=cfg.family,
            eval_every=eval_every,
        ))
        adv = None
        if fl.threat is not None:
            from repro.openworld import threat_state

            ts = threat_state(fl.threat, fl.num_clients)
            adv = np.asarray(ts.adversaries) if ts is not None else None
        graph = SelectionGraph(fl.num_clients, adversaries=adv)
        if trace_stages and strat.spec is not None:
            tracer.write(stage_profile_record(_profile_stages(
                strat, fl, train_data, jax.random.fold_in(key, 1 << 20),
            )))

    round_jit = strat.round            # engine rounds are already jitted
    hist = History()
    clock = RoundClock()
    cum_bytes, cum_net_s, cum_energy = 0, 0.0, 0.0
    cum_device_s = 0.0
    t0 = time.time()

    def consume_round(r, metrics, *, compile_round: bool):
        """Per-round host-side bookkeeping: fabric accounting, History,
        eval, trace record — identical for the per-round and the
        chunked (unstacked) drivers."""
        nonlocal cum_bytes, cum_net_s, cum_energy, cum_device_s
        if strat.fabric is not None:
            stats = strat.fabric.account_round(
                strat.comm_pattern, metrics, payload, name=strat.name
            )
            round_bytes, round_net_s = stats.total_bytes, stats.sim_time_s
            round_energy = stats.energy_j
        else:
            round_bytes, round_net_s, round_energy = 0, 0.0, 0.0
        hist.round_bytes.append(round_bytes)
        hist.round_net_time_s.append(round_net_s)
        cum_bytes += round_bytes
        cum_net_s += round_net_s
        cum_energy += round_energy

        mean_lag, max_lag = _stale_summary(metrics.get("stale"))
        hist.round_stale_lag.append(mean_lag)
        hist.round_stale_max.append(max_lag)

        # simulated device wall-clock: semi-async rounds report their
        # deadline-capped duration; synchronous rounds under a device
        # profile stall on the slowest sampled client
        round_wall = metrics.get("round_wall_s")
        if round_wall is not None:
            round_wall = float(round_wall)
            straggler = float(metrics.get("straggler_wall_s", round_wall))
        elif wall_np is not None:
            act = np.asarray(metrics["active"])
            straggler = float(wall_np[act].max()) if act.any() else 0.0
            round_wall = straggler
        else:
            round_wall = straggler = 0.0
        hist.round_device_wall_s.append(round_wall)
        hist.round_straggler_wall_s.append(straggler)
        eff = metrics.get("eff_lag_mean")
        hist.round_eff_lag.append(float(eff) if eff is not None else 0.0)
        cum_device_s += round_wall

        # every recorded scalar → the generic History.extra channel
        scalars = scalar_metrics(metrics)
        for name, value in scalars.items():
            hist.extra.setdefault(name, []).append(value)

        eval_point = None
        if (r + 1) % eval_every == 0 or r == num_rounds - 1:
            with jax.profiler.TraceAnnotation("evaluate"):
                params = strat.params_for_eval(state)
                if strat.needs_head_finetune:
                    # fold in the round index: each eval point personalizes
                    # on fresh batch draws instead of replaying the same
                    # k_ft ones
                    params = _finetune_heads(
                        cfg, fl, params, data["train_x"], data["train_y"],
                        jax.random.fold_in(k_ft, r),
                    )
                acc, accs = evaluate_population(
                    cfg, params, data["test_x"], data["test_y"]
                )
            if eval_mask is not None:
                kept = np.asarray(accs)[np.asarray(eval_mask, bool)]
                acc = float(kept.mean()) if kept.size else float("nan")
            loss_keys = [k for k in metrics if "loss" in k]
            tl = float(np.mean([float(metrics[k]) for k in loss_keys])) \
                if loss_keys else float("nan")
            hist.rounds.append(r + 1)
            hist.accuracy.append(float(acc))
            hist.train_loss.append(tl)
            hist.wall_s.append(clock.elapsed())
            hist.comm_bytes.append(cum_bytes)
            hist.net_time_s.append(cum_net_s)
            hist.energy_j.append(cum_energy)
            hist.device_time_s.append(cum_device_s)
            eval_point = {"accuracy": float(acc), "train_loss": tl}
            if verbose:
                print(
                    f"[{strategy_name:16s}] round {r + 1:4d} "
                    f"acc={float(acc):.4f} loss={tl:.4f} "
                    f"comm={cum_bytes / 1e6:.2f}MB "
                    f"net={cum_net_s:.1f}s "
                    f"({time.time() - t0:.0f}s)",
                    flush=True,
                )

        if tracer is not None:
            mask = metrics.get("select_mask", metrics.get("comm_edges"))
            edges = graph.observe(mask) if mask is not None else None
            tracer.write(round_record(
                rnd=r, wall_s=clock.last_s, compile_round=compile_round,
                active=int(np.asarray(metrics["active"]).sum()),
                stale_mean=mean_lag, stale_max=max_lag,
                comm={"bytes": round_bytes, "net_time_s": round_net_s,
                      "energy_j": round_energy},
                device={"wall_s": round_wall, "straggler_s": straggler,
                        "eff_lag": hist.round_eff_lag[-1]},
                metrics=scalars, score=score_block(scalars),
                edges=sorted(edges) if (trace_edges and edges is not None)
                else None,
                eval_point=eval_point,
            ))

    # several devices (or a mesh set by the caller): the rounds run under
    # the population mesh, with the client axis of the state and of the
    # data sharded over it
    with (jax.set_mesh(mesh) if mesh is not None
          else contextlib.nullcontext()):
        if chunk_rounds > 1:
            # chunked driver: scan-over-rounds, one jit per DISTINCT
            # chunk size (sizes only vary at eval boundaries / the tail),
            # per-round metrics unstacked from the scan axis into the
            # same consumer
            multi_fns: dict = {}
            r0, chunk_i = 0, 0
            while r0 < num_rounds:
                # chunks END at eval boundaries so evaluation always sees
                # the population state right after the eval round
                boundary = min(((r0 // eval_every) + 1) * eval_every,
                               num_rounds)
                size = min(chunk_rounds, boundary - r0)
                fn = multi_fns.get(size)
                if fn is None:
                    fn = multi_fns[size] = make_multi_round(
                        strat.spec, fl, strat.fabric, chunk_rounds=size
                    )
                with clock.chunk(size):
                    with jax.profiler.TraceAnnotation("dispatch"):
                        state, stacked = fn(state, train_data, k_rounds,
                                            jnp.int32(r0))
                    with jax.profiler.TraceAnnotation("wait"):
                        jax.block_until_ready((state, stacked))
                if chunk_i == 0:
                    hist.compile_s = clock.compile_s
                with jax.profiler.TraceAnnotation("fetch_metrics"):
                    stacked = jax.device_get(stacked)
                with jax.profiler.TraceAnnotation("consume"):
                    for i in range(size):
                        consume_round(
                            r0 + i,
                            jax.tree_util.tree_map(lambda v, i=i: v[i],
                                                   stacked),
                            compile_round=(chunk_i == 0),
                        )
                r0 += size
                chunk_i += 1
        else:
            for r in range(num_rounds):
                k_r = jax.random.fold_in(k_rounds, r)
                with clock.round():
                    with jax.profiler.TraceAnnotation("dispatch"):
                        state, metrics = round_jit(state, train_data, k_r)
                    # fence so the clock sees execution, not async dispatch
                    with jax.profiler.TraceAnnotation("wait"):
                        jax.block_until_ready((state, metrics))
                if r == 0:
                    hist.compile_s = clock.compile_s
                with jax.profiler.TraceAnnotation("fetch_metrics"):
                    metrics = jax.device_get(metrics)
                with jax.profiler.TraceAnnotation("consume"):
                    consume_round(r, metrics, compile_round=(r == 0))

    if tracer is not None:
        if graph.rounds > 0:
            tracer.write(graph.to_record())
        tracer.write(summary_record(
            rounds=num_rounds, wall_s=clock.elapsed(),
            compile_s=clock.compile_s,
            final_accuracy=hist.accuracy[-1] if hist.accuracy else None,
            compile_log=compile_log.summary(),
        ))
        tracer.close()
    hist.state = state
    return hist
