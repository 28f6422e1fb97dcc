"""Least work of DisPFL's mask evolution (`mask_evolve_threshold` and
`mask_evolve_apply`), per round.

One pair of calls per parameter leaf of the model (`param_shapes`), over
the whole population's leaf: find the magnitude threshold that keeps
(1 - sparsity) of the entries, then write the new mask and the masked
parameters. At least the bfloat16 leaf and the regrow plane are read once
and the leaf and the mask written once: 6 bytes and a few operations per
entry.
"""
import math


def _leaves(tree):
    if isinstance(tree, tuple):
        return [tree]
    items = tree.values() if isinstance(tree, dict) else tree
    return [s for t in items for s in _leaves(t)]


def calls(cfg: dict, model) -> list:
    out = []
    for shape in _leaves(model.param_shapes(cfg)):
        n = cfg["num_clients"] * math.prod(shape)
        out.append((4.0 * n, 6.0 * n))
    return out
