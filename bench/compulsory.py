"""Compulsory bytes: what any correct implementation has to move.

Computed from the operator as the configuration states it (rows, stored
non-zeros, the stated value type, whether its values are stored or
generated, and whether it is symmetric), never from a plan's format or
operands.  An SpMV has to read every stored value once, read x once and
write y once.  Where the configuration states that A is symmetric, a
format may store the main diagonal and one triangle only, so the values it
has to read are those, ``(nnz + n_diag) / 2``.  Index bytes are not
compulsory: a format may generate its indices.  So a new format or kernel
changes the time and not the yardstick, and a share of the roofline
cannot pass 100%.
"""
from __future__ import annotations

_VALUE_BYTES = {"float64": 8, "float32": 4, "bfloat16": 2, "float16": 2}


def value_bytes(dtype: str) -> int:
    return _VALUE_BYTES[dtype]


def spmv_bytes(n_rows: int, n_cols: int, nnz: int, dtype: str,
               stored_values: bool, n_diag: int | None = None) -> int:
    """``n_diag``: for an operator stated symmetric, its stored values on
    the main diagonal; None for any other."""
    vb = value_bytes(dtype)
    values = nnz if n_diag is None else (nnz + n_diag) // 2
    return (values * vb if stored_values else 0) + (n_cols + n_rows) * vb
