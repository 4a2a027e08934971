"""Tokens returned per decode launch in the window: the slots one launch
serves (``kernel_calls`` is the program's count of decode launches).
Layer: engine.  Moves ``out_tok_per_s``."""


def read(run):
    launches = sum(s.launches for s in run.steps)
    if not launches:
        return None
    return sum(s.tokens for s in run.steps) / launches
