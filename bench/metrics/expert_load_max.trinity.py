"""Load imbalance over this chip's experts: of the rows each held expert
of each expert layer received over the window's steps (the step's
counters), the largest over the mean."""
import numpy as np


def read(run, out):
    rows = out.get("expert_rows")
    if rows is None or not np.size(rows):
        return None
    total = np.asarray(rows, float).sum(axis=0)
    mean = float(total.mean())
    return float(total.max()) / mean if mean > 0 else None
