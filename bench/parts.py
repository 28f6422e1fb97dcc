"""Split an engine stage's device time by the `part:<name>` scopes the
program opens inside it (`repro.obs.timers.part`).

pfeddst's `stage:score_select` holds three parts: `eq6_probe` (the Eq. 6
probe pass), `eq79_select` (Eq. 7-9 scoring and top-k) and `telemetry`
(the Eq. 9 decomposition of the selected edges); `stage:update_context`
holds a `telemetry` part too. An operation's op_name then reads
`.../stage:<stage>/.../part:<part>/...`.

`op_parts` reads those paths from the compiled chunk's HLO text, and
`part_seconds` counts the traced window's device time per
`"<stage>/<part>"` exactly as `tracing.reduce` counts it per stage.
"""
from __future__ import annotations

import re

import tracing

PART = re.compile(r"stage:([A-Za-z0-9_]+)/(?:[^\"]*/)?part:([A-Za-z0-9_]+)")


def op_parts(hlo_text: str) -> dict:
    """{instruction: "<stage>/<part>"} for each instruction of the
    compiled chunk whose op_name holds a part scope inside a stage
    scope."""
    out = {}
    for line in hlo_text.splitlines():
        m = tracing.INSTR.match(line)
        if not m:
            continue
        name, rest = m.groups()
        op = tracing.OP_NAME.search(rest)
        p = PART.search(op.group(1)) if op else None
        if p:
            out[name] = f"{p.group(1)}/{p.group(2)}"
    return out


def part_seconds(events: dict, parts: dict, module: str) -> dict:
    """{"<stage>/<part>": device seconds, summed over devices} of the
    operations of `module` in the traced window."""
    names = {i: (p, None) for i, p in parts.items()}
    return tracing.reduce(events, names, module)["stage_s"]
