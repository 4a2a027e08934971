"""Backend compiles inside the window of a decode cell (``jax.monitoring``
events).  Layer: engine.  Moves ``out_tok_per_s``: a compile stalls every
slot."""


def read(run):
    return len(run.compiles)
