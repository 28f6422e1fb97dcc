"""Device time of local training per round: the `stage:phase_e` and
`stage:phase_h` scopes (pfeddst) or `stage:local_train` (dispfl and the
other gossip strategies)."""

STAGES = ("phase_e", "phase_h", "local_train")


def read(run):
    tr = run.get("trace")
    if not tr or run["rounds"] <= 0:
        return None
    s = [tr["stage_s"][n] for n in STAGES if n in tr["stage_s"]]
    if not s:
        return None
    return 1000.0 * sum(s) / tr["devices"] / run["rounds"]
