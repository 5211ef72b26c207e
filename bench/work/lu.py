"""The least work an exact log-determinant of a dense N x N matrix needs:
an LU factorization (or the paper's condensation, which does the same
multiply-adds), and one read of the matrix.  Fixed by N alone, so it reads
the same whatever route computes it."""


def flops(n: int) -> float:
    """2/3 N^3: one multiply and one add per trailing entry per pivot."""
    return 2.0 / 3.0 * float(n) ** 3


def bytes_moved(n: int, itemsize: int = 4) -> float:
    """The matrix read once from HBM (float32)."""
    return float(itemsize) * float(n) ** 2
