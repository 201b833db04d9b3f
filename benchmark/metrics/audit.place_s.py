"""audit.place_s: seconds per audit placing sweep inputs on the device
(sharded over the mesh, or on one device), from the program's `place`
phase timer (utils/profiling.PhaseTimers), its delta over the window
over the window's audits. Near 0 when every placement is cached. None
where the program has no such phase."""


def read(obs):
    walls = obs.get("audit_walls_s")
    place = (obs.get("phases_s") or {}).get("place")
    if not walls or place is None:
        return None
    return place / len(walls)
