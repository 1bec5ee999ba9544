"""``expert_ffn_roofline`` in the open-loop cell, where it moves
``itl_p95_ms``."""


def read(c):
    return c.expert_ffn_roofline()
