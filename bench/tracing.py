"""Reduce a profiler trace of the measured window to what the per-layer
metrics read.

`extract` turns the profiler's `.xplane.pb` into plain lists: for each
TPU device the operations of its "XLA Ops" line (HLO instruction, start,
duration in ns, module), and the harness's own host spans (`dispatch`,
`wait`, `fetch_metrics`). `reduce` then works on those lists alone, so a recorded
trace can be checked without a chip:

* the window runs from the start of the first `dispatch` span to the end
  of the last `fetch_metrics` span;
* busy time is the union of a device's operation intervals inside the
  window, averaged over the devices;
* an operation belongs to the engine stage whose `stage:<name>` scope its
  HLO instruction carries in the compiled chunk (`op_names`), and to a
  Pallas kernel when its instruction is that kernel's custom call.
"""
from __future__ import annotations

import glob
import os
import re

HOST_SPANS = ("dispatch", "wait", "fetch_metrics")
STAGE = re.compile(r"stage:([A-Za-z0-9_]+)")
INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([A-Za-z0-9_.\-]+)\s*=\s*(.*)$")
OP_NAME = re.compile(r'op_name="([^"]*)"')


def extract(trace_dir: str) -> dict:
    """{"devices": {plane: [[instruction, start_ns, dur_ns, module], ...]},
    "host": [[span, start_ns, dur_ns], ...]} of the newest trace under
    `trace_dir`. A TPU names each event of its "XLA Ops" line by the
    HLO instruction's text; the module is the "XLA Modules" event the
    operation lies in."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    devices, host = {}, []
    for plane in pd.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            lines = {line.name: list(line.events) for line in plane.lines}
            mods = sorted((float(e.start_ns), float(e.start_ns + e.duration_ns),
                           e.name.split("(")[0])
                          for e in lines.get("XLA Modules", ()))
            ops = []
            for ev in lines.get("XLA Ops", ()):
                s0 = float(ev.start_ns)
                mod = next((n for a, b, n in mods if a <= s0 < b), "")
                ops.append([instruction(ev.name), s0, float(ev.duration_ns),
                            mod])
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in HOST_SPANS:
                        host.append([ev.name, float(ev.start_ns),
                                     float(ev.duration_ns)])
    return {"devices": devices, "host": sorted(host, key=lambda e: e[1])}


def instruction(event_name: str) -> str:
    """'%fusion.12 = bf16[...] fusion(...)' → 'fusion.12'."""
    m = INSTR.match(event_name)
    return m.group(1) if m else event_name


def module_name(hlo_text: str) -> str:
    m = re.match(r"\s*HloModule\s+([A-Za-z0-9_.\-]+)", hlo_text)
    return m.group(1) if m else ""


def op_names(hlo_text: str) -> dict:
    """{instruction: (stage or None, kernel or None)} from the compiled
    chunk's HLO text: the stage from its op_name metadata, the kernel
    from a Mosaic custom call's instruction name."""
    out = {}
    for line in hlo_text.splitlines():
        m = INSTR.match(line)
        if not m:
            continue
        name, rest = m.groups()
        s = STAGE.search(OP_NAME.search(rest).group(1)) \
            if OP_NAME.search(rest) else None
        kernel = None
        if 'custom_call_target="tpu_custom_call"' in rest:
            kernel = name.rsplit(".", 1)[0]
        out[name] = (s.group(1) if s else None, kernel)
    return out


def _union(intervals) -> float:
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def reduce(events: dict, names: dict, module: str) -> dict:
    """→ window_s, busy_s (mean over devices), per-stage and per-kernel
    device seconds (summed over devices) of the operations of `module`,
    top ops, idle gaps (device 0) labelled by the host span in which
    they fall."""
    host = events["host"]
    starts = [s for n, s, _ in host if n == "dispatch"]
    ends = [s + d for n, s, d in host if n == "fetch_metrics"]
    if not starts or not ends:
        raise ValueError("trace holds no dispatch/fetch_metrics spans")
    w0, w1 = min(starts), max(ends)
    stage_s, kernel_s, op_s, busy = {}, {}, {}, []
    gaps = []
    for plane, ops in sorted(events["devices"].items()):
        iv = []
        for name, s, d, mod in _leaves(ops):
            a, b = max(s, w0), min(s + d, w1)
            if b <= a:
                continue
            iv.append((a, b))
            sec = (b - a) * 1e-9
            stage, kernel = names.get(name, (None, None)) \
                if mod == module else (None, None)
            if stage:
                stage_s[stage] = stage_s.get(stage, 0.0) + sec
            if kernel:
                kernel_s[kernel] = kernel_s.get(kernel, 0.0) + sec
            key = f"{stage or mod or '-'}/{name}"
            op_s[key] = op_s.get(key, 0.0) + sec
        busy.append(_union(iv) * 1e-9)
        if not gaps:
            gaps = _gaps(iv, w0, w1, host)
    top = sorted(op_s.items(), key=lambda kv: -kv[1])[:10]
    return {"window_s": (w1 - w0) * 1e-9,
            "busy_s": sum(busy) / max(len(busy), 1),
            "devices": len(busy), "stage_s": stage_s, "kernel_s": kernel_s,
            "device_ops": [[k, v] for k, v in top], "idle_gaps": gaps}


def _leaves(ops) -> list:
    """The operations that hold no other: a loop or call that spans its
    body's operations would count their time twice."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    holds, stack = [False] * len(ops), []
    for i, (_, s, _, _) in enumerate(ops):
        while stack and ops[stack[-1]][1] + ops[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            holds[stack[-1]] = True
        stack.append(i)
    return [o for o, h in zip(ops, holds) if not h]


def _gaps(iv, w0, w1, host, n: int = 10) -> list:
    """The n longest idle intervals of one device, each named by the host
    span that holds its midpoint ("none" outside every span)."""
    out, end = [], w0
    for s, e in sorted(iv):
        if s > end:
            out.append((end, s))
        end = max(end, e)
    if w1 > end:
        out.append((end, w1))
    out.sort(key=lambda g: g[0] - g[1])
    named = []
    for a, b in out[:n]:
        mid = (a + b) / 2
        what = next((nm for nm, s, d in host if s <= mid <= s + d), "none")
        named.append([what, (b - a) * 1e-9])
    return named
