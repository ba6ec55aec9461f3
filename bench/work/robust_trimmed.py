"""Work of one ``robust_trimmed`` call: per coordinate of an (M, P) float32
update matrix, the mean of the participating values with the k smallest and
k largest dropped (k = floor((n-1)/2): the coordinate-wise median).

Bytes: the matrix and the (M,) mask read once, the P outputs written once,
4 bytes each, whether the values are sorted or selected.  FLOPs: ranking
the M values of a coordinate by pairwise comparison, M*M, plus the keep
test and the sum, 2M, and the division, counted as operations."""


def work(m, p):
    """``(flops, bytes)`` of one call on an (m, p) matrix."""
    return p * (m * m + 2 * m + 1), 4 * (m * p + m + p)
