"""Device time per round of the `stage:score_select` scope: the Eq. 6
probe pass and the Eq. 7-9 selection."""


def read(run):
    tr = run.get("trace")
    if not tr or run["rounds"] <= 0 or "score_select" not in tr["stage_s"]:
        return None
    return 1000.0 * tr["stage_s"]["score_select"] / tr["devices"] \
        / run["rounds"]
