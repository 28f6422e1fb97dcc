"""Device time per round of the Eq. 6 probe pass: the `part:eq6_probe`
scope inside pfeddst's `stage:score_select` (bench/parts.py)."""

PART = "score_select/eq6_probe"


def read(run):
    tr = run.get("trace")
    if not tr or run["rounds"] <= 0 or PART not in tr.get("part_s", {}):
        return None
    return 1000.0 * tr["part_s"][PART] / tr["devices"] / run["rounds"]
