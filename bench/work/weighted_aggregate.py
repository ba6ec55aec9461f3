"""Work of one ``weighted_aggregate`` call: out[p] = sum_m scale[m] x[m, p]
over an (M, P) float32 update matrix.  2 FLOPs per element; the matrix and
the M scales are read once and the P outputs written once, 4 bytes each."""


def work(m, p):
    """``(flops, bytes)`` of one call on an (m, p) matrix."""
    return 2 * m * p, 4 * (m * p + m + p)
