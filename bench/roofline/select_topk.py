"""Least work of the fused Eq. 7-9 selection (`select_topk`), per round.

One call per round over the whole population: the cosine Gram of the
(M, P) float32 headers, the recency and score arithmetic on (M, M), and a
per-row top-k. Each operand is read once and each output written once:
headers (M, P) f32, loss array (M, M) f32, recency array (M, M) i32, the
Eq. 9 cost (M, M) f32 and candidate mask (M, M) i8; out (M, k) values and
indices and (M, 2) row statistics.
"""


def header_width(cfg: dict) -> int:
    return cfg["cnn_width"] * 2 ** (len(cfg["cnn_stages"]) - 1) \
        * cfg["num_classes"] + cfg["num_classes"]


def calls(cfg: dict) -> list:
    m, p = cfg["num_clients"], header_width(cfg)
    k = min(cfg["peers_per_round"], m - 1)
    ops = 2.0 * m * m * p + 10.0 * m * m
    nbytes = 4.0 * m * p + (4 + 4 + 4 + 1) * m * m + 8.0 * m * k + 8.0 * m
    return [(ops, nbytes)]
