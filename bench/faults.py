"""Faults planted in the program, to show that the comparison catches
them (bench/calibrate.py on the chip, bench/tests on the CPU).

    half_batch  local training takes the task loss over the first half
                of each batch's rows and leaves the rest out (the whole
                batch still runs forward, so the program keeps its
                shapes and fits the chip at M = 100)
    answer      an answer altered where it is produced: pfeddst's top-k
                selection points every pick at the next client; dispfl's
                evolved mask has every 64th entry flipped
    frozen      the chunk returns the state it was given

Each is a context manager that patches the program's modules and
restores them on exit.
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp


@contextlib.contextmanager
def _patched(module, name, value):
    old = getattr(module, name)
    setattr(module, name, value)
    try:
        yield
    finally:
        setattr(module, name, old)


def _half_batch():
    from repro.models import model

    def half(cfg, params, batch, **kw):
        logits, _ = model.forward(cfg, params, batch, **kw)
        n = logits.shape[0] // 2
        loss = model._task_loss(cfg, logits[:n], jax.tree_util.tree_map(
            lambda x: x[:n], batch))
        return loss, {"loss": loss}

    return _patched(model, "loss_fn", half)


def _answer(strategy: str):
    if strategy == "pfeddst":
        from repro.core import rounds

        to_mask = rounds.topk_to_mask

        def shifted(idx, vals, m):
            return to_mask((idx + 1) % m, vals, m)

        return _patched(rounds, "topk_to_mask", shifted)
    from repro.kernels import ops

    evolve = ops.mask_evolve

    def flipped(x, grow, **kw):
        p, mask = evolve(x, grow, **kw)
        flip = (jnp.arange(mask.size) % 64 == 0).reshape(mask.shape)
        mask = mask ^ flip
        return x * mask.astype(x.dtype), mask

    return _patched(ops, "mask_evolve", flipped)


def _frozen():
    from repro.fl import engine

    make = engine.make_multi_round

    def frozen(spec, fl, fabric=None, **kw):
        fn = make(spec, fl, fabric, jit=False, **kw)

        def keep(state, data, key, start):
            _, stacked = fn(state, data, key, start)
            return state, stacked

        return jax.jit(keep)

    return _patched(engine, "make_multi_round", frozen)


def planted(name: str, strategy: str):
    if name == "half_batch":
        return _half_batch()
    if name == "answer":
        return _answer(strategy)
    if name == "frozen":
        return _frozen()
    raise KeyError(f"no fault {name!r}")
