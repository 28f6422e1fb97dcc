"""Least work of the packed gossip mix (`gossip_mix`), per round.

One call per round over the packed float32 extractors (M, F): each output
row is the weighted sum of its D neighbourhood rows. Reading the input once
and writing the output once is the least traffic; the multiply-adds are
2 * M * D * F.
"""


def extractor_params(cfg: dict) -> int:
    ws = [cfg["cnn_width"] * 2 ** i for i in range(len(cfg["cnn_stages"]))]
    n = 9 * cfg["image_channels"] * ws[0] + 2 * ws[0]
    cin = ws[0]
    for blocks, cout in zip(cfg["cnn_stages"], ws):
        for _ in range(blocks):
            n += 9 * cin * cout + 9 * cout * cout + 4 * cout
            if cin != cout:
                n += cin * cout
            cin = cout
    return n


def degree(cfg: dict) -> int:
    """Neighbourhood slots per row: the ring's two neighbours and self."""
    return min(3, cfg["num_clients"])


def calls(cfg: dict) -> list:
    m, f, d = cfg["num_clients"], extractor_params(cfg), degree(cfg)
    return [(2.0 * m * d * f, 8.0 * m * f + 8.0 * m * d)]
