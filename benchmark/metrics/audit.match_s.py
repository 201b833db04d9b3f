"""audit.match_s: seconds per audit spent matching constraints to the
inventory (each kind's candidate objects and match mask), from the
program's `match` phase timer, its delta over the window over the
window's audits."""


def read(obs):
    walls = obs.get("audit_walls_s")
    match = (obs.get("phases_s") or {}).get("match")
    if not walls or match is None:
        return None
    return match / len(walls)
