"""Device time per round of the Eq. 7-9 scoring and top-k selection: the
`part:eq79_select` scope inside pfeddst's `stage:score_select`
(bench/parts.py)."""

PART = "score_select/eq79_select"


def read(run):
    tr = run.get("trace")
    if not tr or run["rounds"] <= 0 or PART not in tr.get("part_s", {}):
        return None
    return 1000.0 * tr["part_s"][PART] / tr["devices"] / run["rounds"]
