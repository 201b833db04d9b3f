"""audit.handle_violations_s: seconds per audit the client spent
extracting the resource of every result, from the program's
`handle_violations` phase timer (utils/profiling.PhaseTimers), its delta
over the window over the window's audits. None where the program has no
such phase."""


def read(obs):
    walls = obs.get("audit_walls_s")
    handle = (obs.get("phases_s") or {}).get("handle_violations")
    if not walls or handle is None:
        return None
    return handle / len(walls)
