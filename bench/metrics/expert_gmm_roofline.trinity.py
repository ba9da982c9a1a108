"""The grouped matmul's share of its roofline in the traced steps: the
least time its calls could take (operations over the bf16 peak or bytes
over the HBM bandwidth, whichever is longer, for each expert layer's
calls, which all do the same work; the work from the rows the step's
counters report, ``flops_afmoe.gmm_work``) over the device time of the
kernel's operations in the trace (named at set-up from the compiled
step's HLO)."""
import numpy as np

from flops_afmoe import gmm_work
from metrics._shared import peak


def read(run, out):
    red = run.reduced
    names = set(out.get("kernel_ops") or [])
    traced = out.get("traced_steps") or []
    rows = out.get("expert_rows")
    if red is None or not names or not any(traced) or rows is None:
        return None
    busy = sum(e - s for n, s, e in red.ops
               if n.split(":", 1)[-1] in names) / 1e9
    if busy <= 0:
        return None
    kind = run.devices[0].device_kind
    fpeak, bw = peak(kind, "bf16_flops_per_s"), peak(kind, "hbm_bytes_per_s")
    least = 0.0
    for step_rows, on in zip(rows, traced):
        for layer_rows in (step_rows if on else []):
            ops, byts = gmm_work(run.config, [float(np.sum(layer_rows))])
            least += max(ops / fpeak, byts / bw)
    return 100.0 * least / busy
