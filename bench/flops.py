"""Model FLOPs of one federated round, counted from the configuration.

Only the work the algorithm requires counts: multiply-adds of the
convolutions and the header (2 FLOPs each) at the taps that fall inside
the image, for the forward pass, the weight gradients of what is trained,
and the input gradients that reach a trained layer. Recomputation,
padding, normalisation and element-wise work do not count.
"""
from __future__ import annotations

import math


def _taps(n_out: int, n_in: int, k: int, stride: int) -> int:
    """Kernel taps inside the input, summed over one axis of outputs
    (SAME padding)."""
    pad = max((n_out - 1) * stride + k - n_in, 0) // 2
    return sum(1 for o in range(n_out) for t in range(k)
               if 0 <= o * stride + t - pad < n_in)


def layers(cfg: dict) -> list:
    """[(name, macs per image)] of every product of the forward pass, in
    order; the header last."""
    size, ch = cfg["image_size"], cfg["image_channels"]
    ws = [cfg["cnn_width"] * 2 ** i for i in range(len(cfg["cnn_stages"]))]
    out = []

    def conv(name, n_in, cin, cout, k, stride):
        n_out = math.ceil(n_in / stride)
        out.append((name, _taps(n_out, n_in, k, stride) ** 2 * cin * cout))
        return n_out

    n = conv("stem", size, ch, ws[0], 3, 1)
    cin = ws[0]
    for si, (blocks, cout) in enumerate(zip(cfg["cnn_stages"], ws)):
        for bi in range(blocks):
            stride = 2 if (bi == 0 and si > 0) else 1
            n_in = n
            n = conv(f"s{si}b{bi}.conv1", n_in, cin, cout, 3, stride)
            conv(f"s{si}b{bi}.conv2", n, cout, cout, 3, 1)
            if cin != cout:
                conv(f"s{si}b{bi}.proj", n_in, cin, cout, 1, stride)
            cin = cout
    out.append(("head", cin * cfg["num_classes"]))
    return out


def forward(cfg: dict) -> float:
    """FLOPs of one image's forward pass."""
    return 2.0 * sum(m for _, m in layers(cfg))


def train_full(cfg: dict) -> float:
    """One image's forward and backward, every layer trained: weight
    gradients everywhere, input gradients everywhere but the stem."""
    ls = layers(cfg)
    fwd = sum(m for _, m in ls)
    return 2.0 * (3 * fwd - ls[0][1])


def train_header(cfg: dict) -> float:
    """One image with the extractor frozen: the forward pass and the
    header's weight gradient."""
    return forward(cfg) + 2.0 * layers(cfg)[-1][1]


def n_active(cfg: dict) -> int:
    return max(1, int(round(cfg["num_clients"] * cfg["client_sample_ratio"])))


def per_round(cfg: dict) -> dict:
    """{part: FLOPs} of one round of the cell's strategy."""
    n, b = n_active(cfg), cfg["batch_size"]
    spe = cfg["steps_per_epoch"]
    steps_e = cfg["epochs_extractor"] * spe
    if cfg["strategy"] == "pfeddst":
        # phase e trains the extractor under a frozen header: the header
        # still passes the gradient back, and its own weight gradient
        # is not needed
        ls = layers(cfg)
        phase_e = train_full(cfg) - 2.0 * ls[-1][1]
        return {
            "phase_e": n * steps_e * b * phase_e,
            "phase_h": n * cfg["epochs_header"] * spe * b
            * train_header(cfg),
            "probe": n * cfg["num_clients"] * cfg["probe_size"]
            * forward(cfg),
        }
    if cfg["strategy"] == "dispfl":
        return {"local_train": n * steps_e * b * train_full(cfg)}
    raise KeyError(f"no FLOP count for strategy {cfg['strategy']!r}")


def round_flops(cfg: dict) -> float:
    return sum(per_round(cfg).values())
