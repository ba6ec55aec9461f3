"""Work of one ``glr_step`` call over ``rows`` tenants, from shapes.

The algorithm (the GLR change-point step of GLR-CUCB, Alg. 2): append one
reward per scheduled channel to its window of the last ``h`` samples, then
evaluate the GLR statistic of each of the ``n`` channels at every split
point of its window and take the maximum.

Per split s of a window of length n, with prefix sum P and total W:
mu = W/n, P/s, (W-P)/(n-s) [4 ops]; two Bernoulli KLs, each two clips of
two arguments [4], p/q, log, p*log, 1-p, 1-q, (1-p)/(1-q), log, (1-p)*log,
one add [9], so 26; s*kl + (n-s)*kl [4]; the validity test and the
running max [3]: 37 operations, each arithmetic op, comparison or
transcendental counted as one.  The append costs 6 per channel.

Bytes: the window (prefix ring) of every channel is read once, 4 bytes a
sample; per channel the count, total, base, reward and schedule flag are
read and the written slot, total, base and statistic are written: 8 more
words.  The window is not rewritten: only one slot per channel changes.
"""

OPS_PER_SPLIT = 37
OPS_PER_APPEND = 6
WORDS_PER_CHANNEL = 8


def work(rows, n, h):
    """``(flops, bytes)`` of one call on ``rows`` tenants of ``n``
    channels with ``h``-sample windows."""
    flops = rows * n * (h * OPS_PER_SPLIT + OPS_PER_APPEND)
    bytes_ = 4 * rows * n * (h + WORDS_PER_CHANNEL)
    return flops, bytes_
