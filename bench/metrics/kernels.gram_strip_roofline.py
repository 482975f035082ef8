"""kernels.gram_strip_roofline: the K-row Eq. 9 strip kernel's least
time over its device time.

The least time is the larger of the algorithm's FLOPs over the bf16
peak and its bytes over the HBM bandwidth, both counted from the
unpadded shapes (``peaks.gram_strip_counts``: K refreshed rows against
N clients of C classes).  The device time is the mean duration of the
strip kernel's events in the trace.  The kernel carries no name of its
own there, so it is found by its signature: a ``tpu_custom_call`` that
returns one 2-D float32 strip from four operands, two of them the
(rows, 2) [norm, Ĥ] stats.
"""
import re

from benchlib import peaks

_OUT = re.compile(r"^%\S+ = f32\[\d+,\d+\]\{[^}]*\} custom-call\(")
_STATS = re.compile(r"f32\[\d+,2\]")


def is_strip(hlo: str) -> bool:
    if not _OUT.match(hlo) or 'custom_call_target="tpu_custom_call"' \
            not in hlo:
        return False
    operands = hlo.split(" custom-call(", 1)[1].split("), ", 1)[0]
    return operands.count("%") == 4 and len(_STATS.findall(operands)) == 2


def read(rec):
    tr = rec["trace"]
    strips = {name for name, hlo in tr.custom_calls.items()
              if is_strip(hlo)}
    t, n = tr.op_time(lambda name: name in strips)
    if n == 0 or t <= 0.0:
        return None
    wl = rec["cell"].workload
    counts = peaks.gram_strip_counts(int(wl["num_clients"]),
                                     int(wl["num_select"]),
                                     int(rec["cell"].config["num_classes"]))
    least = peaks.roofline_seconds(counts, peaks.peak(rec["device_kind"]))
    return 100.0 * least["seconds"] / (t / n)
