"""Model operations of every prompt and output token processed in the
window (from the configuration's shapes, attention over each token's
context included) over the window times the chip's bf16 peak, in percent.
Layer: model step.  Moves ``out_tok_per_s``."""
from bench.core import costs


def read(run):
    flops = 0.0
    for s in run.steps:
        flops += sum(costs.prefill_flops(run.config, p) for p in s.prefills)
        flops += sum(costs.token_flops(run.config, c) for c in s.decode_ctx)
    if not flops:
        return None
    return 100.0 * flops / (run.seconds * run.peaks["bf16_flops_per_s"])
