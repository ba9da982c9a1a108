"""Device time of the surface program per re-plan, in the traced part
of the window: the lane programs' device seconds over the re-plans
(committed or silent) that ran while the trace was on."""
from metrics._shared import LANE_PROGRAMS


def read(run, out):
    red = run.reduced
    n = out.get("traced_replans", 0)
    if red is None or not n or not red.program_count(LANE_PROGRAMS):
        return None
    return 1e3 * red.program_seconds(LANE_PROGRAMS) / n
