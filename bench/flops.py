"""Model FLOPs of one federated round, counted from the configuration.

Only the work the algorithm requires counts. The cell's model gives the
FLOPs of one example (bench/models/<model>.py `flops`): its forward pass,
a training step of the whole model, of the extractor under a frozen
header, and of the header alone. A round of each strategy is composed
from those here.
"""
from __future__ import annotations


def n_active(cfg: dict) -> int:
    return max(1, int(round(cfg["num_clients"] * cfg["client_sample_ratio"])))


def per_round(cfg: dict, model) -> dict:
    """{part: FLOPs} of one round of the cell's strategy."""
    f = model.flops(cfg)
    n, b = n_active(cfg), cfg["batch_size"]
    spe = cfg["steps_per_epoch"]
    steps_e = cfg["epochs_extractor"] * spe
    if cfg["strategy"] == "pfeddst":
        return {
            "phase_e": n * steps_e * b * f["train_extractor"],
            "phase_h": n * cfg["epochs_header"] * spe * b
            * f["train_header"],
            "probe": n * cfg["num_clients"] * cfg["probe_size"]
            * f["forward"],
        }
    if cfg["strategy"] == "dispfl":
        return {"local_train": n * steps_e * b * f["train_full"]}
    raise KeyError(f"no FLOP count for strategy {cfg['strategy']!r}")


def round_flops(cfg: dict, model) -> float:
    return sum(per_round(cfg, model).values())
