"""Least work of the packed gossip mix (`gossip_mix`), per round.

One call per round over the packed float32 extractors (M, F): each output
row is the weighted sum of its D neighbourhood rows. Reading the input once
and writing the output once is the least traffic; the multiply-adds are
2 * M * D * F. F is the model's extractor size (`extractor_params`).
"""


def degree(cfg: dict) -> int:
    """Neighbourhood slots per row: the ring's two neighbours and self."""
    return min(3, cfg["num_clients"])


def calls(cfg: dict, model) -> list:
    m, f, d = cfg["num_clients"], model.extractor_params(cfg), degree(cfg)
    return [(2.0 * m * d * f, 8.0 * m * f + 8.0 * m * d)]
