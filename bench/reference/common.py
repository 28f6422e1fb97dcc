"""Pieces every reference round shares: the round's random streams, local
SGD of one client at a time, the Eq. 6 probe losses, neighbourhood
weights, and the per-leaf norms that the comparison reads.

The random draws follow the documented semantics of the rounds this
repository defines: round r's key is `fold_in(round_key, r)`, split into
the strategy's named streams in order; the sampled clients are the first
round(M * ratio) of a permutation; client i's batch at a step is drawn
with the i-th of M keys split from that step's key, uniformly with
replacement from its own training examples.

The model is the cell's (bench/models/<model>.py): a batch is the dict of
arrays its data holds, one client's row of each, and its
`example_losses(params, batch, sizes, quant)` gives the plain
reference's loss of each example. Where the model brings weights that
every client shares, every loss reads them as given (`bind_losses`); the
reference keeps no optimizer state over them and never updates them.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
NEG = -1e30


_JITTED = {}


def jitted(round_fn, cfg: dict, quant, model, **consts):
    """One compiled reference round per (round, sizes, precision, model)
    in a process; the state argument is consumed."""
    key = (round_fn, json.dumps(cfg, sort_keys=True), quant, model)
    if key not in _JITTED:
        _JITTED[key] = jax.jit(functools.partial(
            round_fn, cfg=cfg, quant=quant, model=model, **consts),
            donate_argnums=(0,))
    return _JITTED[key]


NO_SHARED = "the program cannot hold weights that every client shares"


def hold_shared(state, shared):
    """The program's initial state with the shared weights in its place
    `shared`, a field of a named tuple or a key of a dict; the state as
    it is where there are none. Raises ValueError where the state has no
    such place."""
    if shared is None:
        return state
    if isinstance(state, dict) and "shared" in state:
        return {**state, "shared": shared}
    if "shared" in getattr(state, "_fields", ()):
        return state._replace(shared=shared)
    raise ValueError(f"{NO_SHARED}: its state ({type(state).__name__}) has "
                     f"no place `shared`")


def bind_losses(model, cfg: dict, quant, shared):
    """`losses(p, batch, q=quant)`: the model's loss of each example of
    one client's batch under its weights `p`, and the shared weights
    where the model has them."""
    kw = {} if shared is None else {"shared": shared}

    def losses(p, batch, q=quant):
        return model.example_losses(p, batch, cfg, q, **kw)

    return losses


def streams(key, r, names):
    ks = jax.random.split(jax.random.fold_in(key, r), len(names))
    return dict(zip(names, ks))


def sample_clients(key, m: int, ratio: float):
    n = max(1, int(round(m * ratio)))
    idx = jax.random.permutation(key, m)[:n]
    return idx, jnp.zeros((m,), bool).at[idx].set(True)


def n_examples(data) -> int:
    """Examples a client holds: the second axis of every (M, n, ...) leaf."""
    return jax.tree_util.tree_leaves(data)[0].shape[1]


def client_batch(key, i, data, m: int, batch: int) -> dict:
    """Client i's batch under `key`: the i-th of M keys."""
    k = jax.random.split(key, m)[i]
    j = jax.random.randint(k, (batch,), 0, n_examples(data))
    return {name: x[i][j] for name, x in data.items()}


def sgd(p, g, mu, hp, quant=None):
    """SGD with momentum and weight decay on one leaf; the update is
    rounded to the parameter's dtype before it is added. The control
    (`quant`) keeps the parameter itself in its lower precision too."""
    gf = g.astype(F32) + hp["weight_decay"] * p.astype(F32)
    mu = hp["momentum"] * mu + gf
    u = (-hp["lr"] * mu).astype(p.dtype)
    p = (p.astype(F32) + u.astype(F32)).astype(p.dtype)
    return (p if quant is None else quant(p)), mu


def train_rows(loss_of, params, mu, frozen, key, idx, data, *, m, steps,
               hp, quant):
    """`steps` SGD steps of the clients `idx`, side by side: row r of
    `params` (the trained part), `mu` and `frozen` (the rest of the
    model, or None) is client idx[r]'s. `loss_of(p, frozen, batch, quant)`
    → (params, mu, each row's loss at the last step)."""
    keys = jax.random.split(key, steps)

    def one(p, mo, fz, b):
        l, g = jax.value_and_grad(lambda q: loss_of(q, fz, b, quant))(p)
        upd = jax.tree_util.tree_map(lambda a, b, c: sgd(a, b, c, hp, quant),
                                     p, g, mo)
        p, mo = (jax.tree_util.tree_map(lambda t: t[k], upd,
                                        is_leaf=lambda t: isinstance(t, tuple))
                 for k in (0, 1))
        return p, mo, l

    def step(carry, k):
        p, mo = carry
        b = jax.vmap(lambda i: client_batch(k, i, data, m,
                                            hp["batch_size"]))(idx)
        p, mo, l = jax.vmap(one)(p, mo, frozen, b)
        return (p, mo), l

    (params, mu), losses = jax.lax.scan(step, (params, mu), keys)
    return params, mu, losses[-1]


def probe_losses(params_rows, data, key, *, m, probe, losses):
    """(R, M): row r's model on each client's probe batch (Eq. 6), one
    row at a time. A row's model runs once, on the M probe batches as one
    batch of M * P examples (`losses(params, batch)` → each example's
    loss), and each client's loss is the mean over its own P examples."""
    keys = jax.random.split(key, m)
    n = n_examples(data)
    j = jax.vmap(lambda k: jax.random.randint(k, (probe,), 0, n))(keys)
    flat = {name: jax.vmap(lambda a, b: a[b])(x, j).reshape(
        (m * probe,) + x.shape[2:]) for name, x in data.items()}

    def row(p):
        return jnp.mean(losses(p, flat).reshape(m, probe), axis=1)

    return jax.lax.map(row, params_rows)


def row_weights(mask):
    """Row-stochastic weights over each row's selected peers and itself."""
    m = mask.shape[0]
    w = jnp.maximum(mask.astype(F32), jnp.eye(m, dtype=F32))
    return w / w.sum(axis=1, keepdims=True)


def mix_rows(tree, w_rows):
    """Rows of W @ tree, summed in float32, rounded to each leaf's dtype."""
    return jax.tree_util.tree_map(
        lambda x: jnp.einsum("ij,j...->i...", w_rows, x.astype(F32),
                             precision=jax.lax.Precision.HIGHEST
                             ).astype(x.dtype), tree)


def take_rows(tree, idx):
    return jax.tree_util.tree_map(lambda x: x[idx], tree)


def put_rows(tree, idx, sub, keep=None):
    """Scatter `sub` into `tree` at `idx`; rows with keep False stay."""
    def put(x, s):
        if keep is not None:
            s = jnp.where(keep.reshape((-1,) + (1,) * (s.ndim - 1)), s,
                          x[idx])
        return x.at[idx].set(s)

    return jax.tree_util.tree_map(put, tree, sub)


def split(params, header: tuple):
    """(extractor, header) of a parameter tree, by the header's top-level
    keys."""
    return ({k: v for k, v in params.items() if k not in header},
            {k: v for k, v in params.items() if k in header})


def merge(e, h):
    return {**e, **h}


# ---------------------------------------------------------------------------
# what the comparison reads
# ---------------------------------------------------------------------------

@jax.jit
def change_norms(after, before):
    """Per leaf, ||after - before|| over the whole stacked leaf."""
    return [jnp.sqrt(jnp.sum(jnp.square(a.astype(F32) - b.astype(F32))))
            for a, b in zip(jax.tree_util.tree_leaves(after),
                            jax.tree_util.tree_leaves(before))]


@jax.jit
def norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(a.astype(F32))))
            for a in jax.tree_util.tree_leaves(tree)]


@jax.jit
def largest_change(after, before):
    """Largest |after - before| over every entry of two trees of one
    layout (not finite where an entry is not)."""
    gaps = jax.tree_util.tree_map(
        lambda a, b: jnp.max(jnp.abs(a.astype(F32) - b.astype(F32))),
        after, before)
    return jnp.max(jnp.stack(jax.tree_util.tree_leaves(gaps)))


def shared_reading(shared, shared0) -> dict:
    """{"shared_changed": the largest change of the shared weights from
    the seed's}, or {} where the model has none."""
    if shared0 is None:
        return {}
    return {"shared_changed": float(largest_change(shared, shared0))}


def shared_number(prog) -> dict:
    """The compared `shared_changed`, where the program's snapshot reads
    one (the reference never updates the shared weights)."""
    return {k: prog[k] for k in ("shared_changed",) if k in prog}


def host(xs) -> np.ndarray:
    return np.asarray(jax.device_get(xs), np.float64)


def leaf_gaps(prog, ref, floor_ref) -> np.ndarray:
    """Per leaf, |prog - ref| against the larger of its reference value
    and the median leaf's. Leaves whose `floor_ref` reading (the
    reference momentum) is under a thousandth of the median are left
    out: only round-off moves them. Not finite → [inf]."""
    prog, ref = np.asarray(prog), np.asarray(ref)
    fl = np.asarray(floor_ref)
    if not (np.isfinite(prog).all() and np.isfinite(ref).all()):
        return np.array([np.inf])
    keep = fl >= 1e-3 * np.median(fl)
    den = np.maximum(ref, np.median(ref))
    return (np.abs(prog - ref) / np.maximum(den, 1e-30))[keep]


def rel_gap_series(prog, ref) -> float:
    """Largest relative gap between two series of losses."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    if not (np.isfinite(prog).all() and np.isfinite(ref).all()):
        return float("inf")
    return float(np.max(np.abs(prog - ref) / np.maximum(np.abs(ref),
                                                        1e-30)))
