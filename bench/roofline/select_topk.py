"""Least work of the fused Eq. 7-9 selection (`select_topk`), per round.

One call per round over the whole population: the cosine Gram of the
(M, P) float32 headers (P the model's `header_width`), the recency and
score arithmetic on (M, M), and a per-row top-k. Each operand is read once
and each output written once: headers (M, P) f32, loss array (M, M) f32,
recency array (M, M) i32, the Eq. 9 cost (M, M) f32 and candidate mask
(M, M) i8; out (M, k) values and indices and (M, 2) row statistics.
"""


def calls(cfg: dict, model) -> list:
    m, p = cfg["num_clients"], model.header_width(cfg)
    k = min(cfg["peers_per_round"], m - 1)
    ops = 2.0 * m * m * p + 10.0 * m * m
    nbytes = 4.0 * m * p + (4 + 4 + 4 + 1) * m * m + 8.0 * m * k + 8.0 * m
    return [(ops, nbytes)]
