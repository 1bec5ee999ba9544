"""Whole step's share of the chips' bf16 peak: useful model operations of
the window (2 x active parameters per token, with the LM head only where a
token is sampled, plus attention over the context really attended) over
window time x chips x peak. Moves ``tokens_per_s``."""


def read(c):
    return c.mfu(busy_only=False)
