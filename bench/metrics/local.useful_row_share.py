"""local.useful_row_share: real rows over the rows the local SGD
steps compute, in the traced call.

Each selected client runs ``epochs`` passes of ⌊cap/B⌋ batches of B
rows drawn from a permutation of its padded rows; of those ⌊cap/B⌋·B
positions a client of n real rows fills n·⌊cap/B⌋·B/cap on average.
Padding rows, and whole batches of padding, are computed and wasted.
Counted from the program's selected ids and the cell's client sizes.
"""
import numpy as np


def read(rec):
    ids = np.asarray(rec["ids"])
    if ids.size == 0:
        return None
    cap = int(rec["cap"])
    bs = min(int(rec["cell"].workload["local"]["batch_size"]), cap)
    used = max(1, cap // bs) * bs
    real = float(np.sum(np.asarray(rec["sizes"])[ids])) * used / cap
    return 100.0 * real / (ids.size * used)
