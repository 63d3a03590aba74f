"""Work the algorithm must do, computed from a problem's shapes."""
from __future__ import annotations

INT_BYTES = 4  # the incidence, degrees and core numbers are int32


def peel_least_bytes(n_r: int, n_s: int, C: int) -> int:
    """Least bytes an exact peel moves: each entry of the (n_s, C)
    incidence, of ``deg0`` (n_r) and of the membership CSR (n_r + 1
    offsets, n_s * C ids) read once, and n_r core numbers written once.
    Any implementation, padded or frontier-compacted, must do this much."""
    entries = n_s * C + n_r + (n_r + 1 + n_s * C) + n_r
    return INT_BYTES * entries
