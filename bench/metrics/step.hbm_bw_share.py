"""Bytes the served work must move, over the window times the chip's HBM
bandwidth, in percent: the weights once per engine iteration that decodes
and once per prefill, and each live slot's cache (K and V rows, or the
recurrent state read and written).  The count does not depend on how many
launches an implementation makes.  Layer: model step.  Moves
``out_tok_per_s``."""
from bench.core import costs


def read(run):
    w = costs.weight_params(run.config) * costs.BF16
    byts = 0.0
    for s in run.steps:
        byts += w * len(s.prefills)
        if s.decode_ctx:
            byts += w + sum(costs.slot_bytes(run.config, c) for c in s.decode_ctx)
    if not byts:
        return None
    return 100.0 * byts / (run.seconds * run.peaks["hbm_bytes_per_s"])
