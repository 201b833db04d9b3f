"""audit.join_s: seconds per audit in the join kinds' key freeze and
device join, from the program's `join` phase timer
(utils/profiling.PhaseTimers), its delta over the window over the
window's audits. None where the program has no such phase."""


def read(obs):
    walls = obs.get("audit_walls_s")
    join = (obs.get("phases_s") or {}).get("join")
    if not walls or join is None:
        return None
    return join / len(walls)
