"""Bytes one interior-point row-iteration of a node LP must move at
least, from the node's shape alone.

A node LP of ``tau`` tasks on ``mu`` platforms has ``tau`` placement rows
and a border of 2*mu latency and quanta rows (one more with a budget,
left out here: this is a floor).  Its standard form has the mu*tau
shares, mu quanta, the makespan and a slack per border row as columns.
Held by its structure, the constraint operator is two (mu, tau) arrays
(placement and border weights of the shares), the border's weights per
platform (2*mu x mu) and its block over the small columns.  A row-
iteration reads that operator once, and reads and writes the iterate
(x, z, w, s over the columns, y over the rows) once.  Values are float64;
the bytes do not depend on how the device computes in that precision.
"""
from __future__ import annotations

F64_BYTES = 8


def per_row_iteration(mu: int, tau: int) -> int:
    """The floor, in bytes, for one row of a stacked solve paying one
    interior-point iteration."""
    m_b = 2 * mu
    rows = tau + m_b
    cols = mu * tau + mu + 1 + m_b
    operator = 2 * mu * tau + m_b * mu + m_b * (mu + 1 + m_b)
    iterate = 4 * cols + rows
    return F64_BYTES * (operator + 2 * iterate)
