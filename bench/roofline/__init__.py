"""Least operations and bytes of each Pallas kernel, from the cell's sizes.

`<kernel>.calls(sizes)` lists (operations, bytes) for every call the round
makes; `least_seconds` turns them into the roofline's least time.
"""


def least_seconds(calls, peak: dict) -> float:
    return sum(max(ops / peak["bf16_flops_per_s"],
                   nbytes / peak["hbm_bytes_per_s"]) for ops, nbytes in calls)
