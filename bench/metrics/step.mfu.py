"""step.mfu: the FLOPs local training requires in the traced call, over
the call's length times the chip's bf16 peak.

Required FLOPs: forward and backward of every real row a local step
trains on, the expected count of ``local.useful_row_share``, each
costing the family's ``train_flops_per_row`` (a classifier's: 6 per
multiply-accumulate of its layers).  Padding rows, selection,
aggregation and evaluation do not count.
"""
import numpy as np

from benchlib import catalog, peaks


def read(rec):
    ids = np.asarray(rec["ids"])
    tr = rec["trace"]
    if ids.size == 0 or tr.window_s <= 0.0:
        return None
    cell = rec["cell"]
    loc = cell.workload["local"]
    cap = int(rec["cap"])
    bs = min(int(loc["batch_size"]), cap)
    used = max(1, cap // bs) * bs
    rows = float(np.sum(np.asarray(rec["sizes"])[ids])) * used / cap \
        * int(loc["epochs"])
    flops = rows * catalog.family(cell.config).train_flops_per_row(
        cell.config, cell.workload)
    pk = peaks.peak(rec["device_kind"])
    return 100.0 * flops / (tr.window_s * pk["bf16_flops"])
