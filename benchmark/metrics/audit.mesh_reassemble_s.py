"""audit.mesh_reassemble_s: seconds per audit merging a mesh sweep's
per-shard result blocks by first row, from the program's `reassemble`
phase timer (utils/profiling.PhaseTimers), its delta over the window
over the window's audits. None where the phase is absent: on one
device, or in a program without it."""


def read(obs):
    walls = obs.get("audit_walls_s")
    merge = (obs.get("phases_s") or {}).get("reassemble")
    if not walls or merge is None:
        return None
    return merge / len(walls)
