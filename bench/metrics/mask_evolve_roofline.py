"""Roofline share of DisPFL's mask evolution, its threshold and apply
kernels together."""
from metrics._roofline import share


def read(run):
    return share(run, "mask_evolve",
                 ("mask_evolve_threshold", "mask_evolve_apply"))
