"""Model FLOP/s utilization of the AFMoE coded train step on its chip's
share: the forward and backward operations per UNIQUE token
(``flops_afmoe.train_flops_per_token``, the held experts' rows as the
step's counters report them, averaged over the window's steps;
replicated coded rows and rematerialized passes do not count), times
the unique tokens of a step over the window's median step time, over the
chip's bf16 peak."""
import numpy as np

from flops_afmoe import train_flops_per_token
from metrics._shared import peak


def read(run, out):
    steps = out.get("step_seconds") or []
    rows = out.get("expert_rows")
    if not steps or rows is None or not np.size(rows):
        return None
    tr = run.traffic
    unique = out["unique_tokens_per_step"]
    held_rows = float(np.sum(rows)) / len(rows) / tr["c"] / unique
    flops = train_flops_per_token(run.config, tr["seq_len"], held_rows)
    rate = unique / float(np.median(steps))
    kind = run.devices[0].device_kind
    return 100.0 * flops * rate / (len(run.devices)
                                   * peak(kind, "bf16_flops_per_s"))
