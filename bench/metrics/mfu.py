"""Model FLOP utilisation of the whole round program: the model FLOPs
the rounds of the traced window required (bench/flops.py, from the
cell's model) over the window's wall time times the chips' bf16 peak."""
import flops


def read(run):
    if run["rounds"] <= 0 or run["window_s"] <= 0:
        return None
    work = flops.round_flops(run["sizes"], run["model"]) * run["rounds"]
    peak = run["peak"]["bf16_flops_per_s"] * run["chips"]
    return 100.0 * work / (run["window_s"] * peak)
