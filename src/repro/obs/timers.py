"""Host-side stage timing — compile/steady split with explicit fencing.

jax dispatch is asynchronous and the engine jits whole rounds, so naive
`time.time()` deltas attribute everything to whichever call happens to
block. The helpers here make wall-time attribution explicit:

* `StageTimes` — accumulates per-label wall times, splitting the FIRST
  call (trace + compile + one execution) from the steady-state mean.
  This is the split BENCH_round.json reports per stage and the
  simulator reports per experiment (History.compile_s vs wall_s).
* `instrument_stages` — wraps engine stages with `block_until_ready`
  fencing + `jax.named_scope`/`jax.profiler.TraceAnnotation` so an
  UNJITTED round attributes host wall to individual stages (inside jit
  the wrappers run once at trace time and measure tracing, not
  execution — run the round with `jit=False` to profile stages).
* `RoundClock` — the whole-round variant the simulator threads through
  `run_experiment`: round 0's wall (compile-dominated) lands in
  `compile_s`, later rounds accumulate into `steady_s`.
* `CompileLog` — what building the programs cost: JAX's own trace,
  lowering, backend-compile and compile-cache durations, as unions of
  their intervals while the log is open.

`jax.named_scope` is also applied by the engine itself around every
stage (`stage:<name>`, jit-compatible: it only attaches XLA metadata),
so device profiles collected with `jax.profiler` group ops by stage even
in the fully-jitted path; `part(name)` opens the `part:<name>` scopes
that split a stage further.
"""
from __future__ import annotations

import time
from contextlib import contextmanager
from dataclasses import dataclass, field

import jax


def stage_name(stage) -> str:
    """Display name of an engine stage callable: the `stage_name`
    attribute the stage factories attach, falling back to __name__."""
    return getattr(stage, "stage_name",
                   getattr(stage, "__name__", "stage"))


def part(name: str):
    """`part:<name>` scope around one part of a stage's work: XLA metadata
    only, so inside the engine's `stage:<stage>` scope a device op's
    op_name reads `.../stage:<stage>/part:<name>/...`."""
    return jax.named_scope(f"part:{name}")


@contextmanager
def annotate(name: str):
    """named_scope (XLA metadata, jit-safe) + TraceAnnotation (host
    profiler track) around a block."""
    with jax.named_scope(name), jax.profiler.TraceAnnotation(name):
        yield


@dataclass
class StageTimes:
    """Per-label wall-time accumulator with a first/steady split.

    first[label]    wall of the label's first observed call — for jitted
                    or scan-traced code this is compile-dominated
    steady[label]   list of subsequent call walls
    """
    first: dict = field(default_factory=dict)
    steady: dict = field(default_factory=dict)

    def add(self, label: str, dt: float, *, rounds: int = 1):
        """Record one observed call of `label` taking `dt` seconds.

        rounds > 1 attributes a CHUNKED call (one jit executing `rounds`
        scanned rounds): the first chunk's whole wall stays the label's
        first-call entry (one compile covered the chunk), later chunks
        contribute their per-round wall `dt / rounds` once per round so
        the steady mean remains per-round comparable across chunk sizes.
        """
        if label not in self.first:
            self.first[label] = dt
        else:
            per_round = dt / rounds
            self.steady.setdefault(label, []).extend([per_round] * rounds)

    @contextmanager
    def timed(self, label: str, *, rounds: int = 1):
        t0 = time.perf_counter()
        yield
        self.add(label, time.perf_counter() - t0, rounds=rounds)

    def summary(self) -> dict:
        """{label: {first_s, steady_s, compile_s, calls}} — compile_s is
        the first-call wall minus the steady mean (floored at 0),
        the same estimator round_bench.py uses for whole rounds."""
        out = {}
        for label, first in self.first.items():
            steady = self.steady.get(label, [])
            steady_s = sum(steady) / len(steady) if steady else 0.0
            out[label] = {
                "first_s": round(first, 6),
                "steady_s": round(steady_s, 6),
                "compile_s": round(max(first - steady_s, 0.0), 6),
                "calls": 1 + len(steady),
            }
        return out


def _fence(*trees):
    """Block until every array in the given pytrees is ready — the
    boundary that makes host wall attributable to the preceding stage."""
    jax.block_until_ready([t for t in trees if t is not None])


def instrument_stages(stages, times: StageTimes):
    """Wrap each engine stage with fencing + timing + profiler scopes.

    Returns a stage tuple suitable for `engine.run_round`. Each wrapped
    stage fences its OUTPUT state and the round context's metrics/aux
    values before stopping its clock, so async dispatch from stage N
    cannot leak into stage N+1's measurement. Meaningful on unjitted
    rounds only (see module docstring).
    """

    def wrap(stage):
        name = stage_name(stage)

        def timed(state, ctx):
            _fence(state)
            t0 = time.perf_counter()
            with annotate(f"stage:{name}"):
                out = stage(state, ctx)
            _fence(out, list(ctx.metrics.values()), list(ctx.aux.values()))
            times.add(name, time.perf_counter() - t0)
            return out

        timed.stage_name = name
        return timed

    return tuple(wrap(s) for s in stages)


@dataclass
class RoundClock:
    """Whole-round wall clock with the round-0 compile split.

    The first `round()` context's wall lands in `compile_s` (the first
    jitted call = trace + XLA compile + one execution); every later
    round accumulates into `steady_s`. `elapsed()` = steady-only wall,
    the number acc-vs-time curves should use (pre-obs History folded the
    compile tax into the first eval point's wall_s).

    Chunked execution (`chunk(n)`, the scan-over-rounds path) keeps the
    same attribution contract at chunk granularity: the FIRST chunk's
    whole wall is `compile_s` — one compile covering trace + XLA + n
    executed rounds, so it is an upper bound on pure compile — and
    later chunks accumulate into `steady_s`. `last_s` always holds the
    PER-ROUND wall of the latest context (chunk wall / n), which is
    what the trace writer records for each unstacked round.
    """
    compile_s: float = 0.0
    steady_s: float = 0.0
    rounds: int = 0
    last_s: float = 0.0

    @contextmanager
    def chunk(self, n: int):
        t0 = time.perf_counter()
        yield
        wall = time.perf_counter() - t0
        self.last_s = wall / n
        if self.rounds == 0:
            self.compile_s = wall
        else:
            self.steady_s += wall
        self.rounds += n

    @contextmanager
    def round(self):
        with self.chunk(1):
            yield

    def elapsed(self) -> float:
        return self.steady_s


TRACE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                "/jax/core/compile/jaxpr_to_mlir_module_duration")
BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_EVENTS = TRACE_EVENTS + (BACKEND_EVENT, CACHE_EVENT)
_open_logs: list = []


def _hear(event, duration, **kw):
    if _open_logs and event in _EVENTS:
        end = time.perf_counter()
        for log in tuple(_open_logs):
            log.events.append((event, end - duration, end))


class CompileLog:
    """The time JAX spends building programs while the log is open.

    JAX reports the duration of every trace, lowering, backend compile
    and persistent-cache retrieval through `jax.monitoring`; each event
    becomes an interval ending when it is heard. Nested jits trace
    inside their callers' traces and a cache retrieval lies inside its
    backend compile, so the seconds are unions of intervals, not sums:

        trace_s     trace and lowering to StableHLO
        compile_s   XLA compiles and cache loads
        compiles    programs XLA compiled
        cache_hits  programs loaded from the persistent cache

    Listeners cannot be unregistered, so one is registered per process
    and feeds the logs that are open. Use as a context manager.
    """
    _registered = False

    def __init__(self):
        self.events: list = []
        if not CompileLog._registered:
            jax.monitoring.register_event_duration_secs_listener(_hear)
            CompileLog._registered = True

    def __enter__(self):
        _open_logs.append(self)
        return self

    def __exit__(self, *exc):
        _open_logs.remove(self)

    def summary(self) -> dict:
        def union(names):
            return _union([(a, b) for e, a, b in self.events if e in names])

        backend = sum(1 for e, _, _ in self.events if e == BACKEND_EVENT)
        hits = sum(1 for e, _, _ in self.events if e == CACHE_EVENT)
        return {"trace_s": union(TRACE_EVENTS),
                "compile_s": union((BACKEND_EVENT, CACHE_EVENT)),
                "compiles": backend - hits, "cache_hits": hits}


def _union(intervals) -> float:
    """Length of the union of (start, end) intervals."""
    total, end = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b > end:
            total += b - max(a, end)
            end = b
    return total
