"""The keys of a run, and DisPFL's masks, made on the device from the seed.

A run's data and initial weights are the cell's model's
(bench/models/<model>.py: `make_train_data`, `init_population`), each
from its own key of `seed_keys`; the weights that every client shares,
where the model has them (`init_shared`), from `shared_key`. Nothing
here imports the program: the reference starts from exactly these
arrays, and the program's state is built around them.
"""
from __future__ import annotations

import jax


def base_key(seed: int):
    """A key from any non-negative seed below 2**64 (the driver's seeds
    pass 32 bits)."""
    lo, hi = seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF
    return jax.random.fold_in(jax.random.PRNGKey(lo), hi)


def seed_keys(seed: int) -> dict:
    """The named keys of one run: data, weights, masks, rounds."""
    ks = jax.random.split(base_key(seed), 4)
    return dict(zip(("data", "weights", "masks", "rounds"), ks))


# folded into the weights' key for the weights every client shares; a fifth
# split of the base key would change the four keys above, and with them
# every cell's data and weights
SHARED_FOLD = 0x5348


def shared_key(weights_key):
    """The key of the weights every client shares, from `seed_keys`'s
    weights key."""
    return jax.random.fold_in(weights_key, SHARED_FOLD)


def init_masks(params, sparsity: float, key):
    """DisPFL's personal masks: each entry kept with probability
    1 - sparsity, one key per leaf."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    ks = jax.random.split(key, len(leaves))
    return jax.tree_util.tree_unflatten(
        treedef, [jax.random.uniform(k, l.shape) > sparsity
                  for l, k in zip(leaves, ks)])
