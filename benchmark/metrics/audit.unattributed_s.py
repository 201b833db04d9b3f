"""audit.unattributed_s: seconds per audit that no phase of the program
covers: the window's summed audit walls less every phase timer's delta
over the window (utils/profiling.PhaseTimers), over the window's audits.
The program's phases do not nest, and the window compiles nothing (a
check holds it), so no background phase enters the sum. None without
audits or without phases."""


def read(obs):
    walls = obs.get("audit_walls_s")
    phases = obs.get("phases_s")
    if not walls or not phases:
        return None
    return (sum(walls) - sum(phases.values())) / len(walls)
