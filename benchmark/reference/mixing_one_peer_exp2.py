"""Mixing matrices of dynamic one-peer Exponential-2 averaging, from the
definition: at step ``t`` the phase is ``k = t mod period`` and rank ``i``
averages itself and rank ``(i - 2^k) mod n`` with weights 1/2 each, the
phases running over the powers of two below ``n``.  One rank: the identity.
Written out here, not read from ``bluefog_tpu.topology``.  ``eager`` names
the library's eager op these matrices describe.
"""

import numpy as np


def eager(x: np.ndarray, t: int) -> np.ndarray:
    """The library's own averaging of the rank-major ``x`` at step ``t``."""
    import bluefog_tpu as bf
    return bf.to_numpy(bf.dynamic_neighbor_allreduce(x, t))


def period(n: int) -> int:
    """Number of phases: the powers of two below ``n`` (1 for one rank)."""
    return max(1, sum(1 for k in range(n.bit_length()) if 2 ** k < n))


def matrix(n: int, t: int) -> np.ndarray:
    """``W_t``, row ``i`` the weights rank ``i`` gives to every rank."""
    if n == 1:
        return np.eye(1)
    w = np.zeros((n, n))
    shift = 2 ** (t % period(n))
    for i in range(n):
        w[i, i] += 0.5
        w[i, (i - shift) % n] += 0.5
    return w
