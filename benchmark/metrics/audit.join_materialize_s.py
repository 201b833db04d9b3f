"""audit.join_materialize_s: seconds per audit rendering the join kinds'
firing pairs (the template's evaluator, which reads the inventory), from
the program's `join_materialize` phase timer
(utils/profiling.PhaseTimers), its delta over the window over the
window's audits. None where the program has no such phase."""


def read(obs):
    walls = obs.get("audit_walls_s")
    mat = (obs.get("phases_s") or {}).get("join_materialize")
    if not walls or mat is None:
        return None
    return mat / len(walls)
