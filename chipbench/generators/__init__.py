"""Traffic generators, one module per ``kind`` of traffic file."""


def seed64(seed: int) -> int:
    """``--seed`` as numpy's seed sequences take it: any whole number,
    negative or wider than 32 bits, folded into 64 bits."""
    return int(seed) & (2 ** 64 - 1)
