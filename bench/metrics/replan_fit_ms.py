"""Host time of a committing ``observe`` call outside the planner call:
the median over commits of the call's wall time minus the commit's own
``ControlEvent.replan_ms`` (estimator fits, detectors, the commit)."""
import numpy as np


def read(run, out):
    commits = out.get("commits") or []
    if not commits:
        return None
    return float(np.median([c["ms"] - c["replan_ms"] for c in commits]))
