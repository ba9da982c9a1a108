"""Model FLOP/s utilization of the coded train step: the forward and
backward operations per UNIQUE token (``flops.train_flops_per_token``;
replicated coded rows and rematerialized passes do not count), times the
unique tokens of a step over the window's median step time, over the
chip's bf16 peak."""
import numpy as np

from flops import train_flops_per_token
from metrics._shared import peak


def read(run, out):
    steps = out.get("step_seconds") or []
    if not steps:
        return None
    rate = out["unique_tokens_per_step"] / float(np.median(steps))
    flops = train_flops_per_token(run.config, run.traffic["seq_len"])
    kind = run.devices[0].device_kind
    return 100.0 * flops * rate / (len(run.devices)
                                   * peak(kind, "bf16_flops_per_s"))
