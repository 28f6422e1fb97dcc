"""Shared arithmetic of the `<kernel>_roofline` readers: the least time
of the kernel's calls in the window (bench/roofline/<kernel>.py, at the
cell's sizes and model) over the device time of its events."""
import importlib

from roofline import least_seconds


def share(run, module: str, kernels: tuple):
    tr = run.get("trace")
    if not tr or run["rounds"] <= 0:
        return None
    spent = sum(tr["kernel_s"].get(k, 0.0) for k in kernels)
    if spent <= 0:
        return None
    calls = importlib.import_module(f"roofline.{module}").calls(
        run["sizes"], run["model"])
    least = least_seconds(calls, run["peak"]) * run["rounds"] \
        * tr["devices"]
    return 100.0 * least / spent
