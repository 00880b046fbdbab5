"""Operations and bytes of one ``pulse_counts`` launch (coincidence counts
of sampled pulse streams).

Streams of ``rows * bl`` slots over ``out`` rows and ``k`` columns: two
(out x rows*bl) x (rows*bl x k) products (net and total).  Bytes: both f32
stream matrices and the two f32 count matrices.
"""


def cost(launch):
    n, k, m, bl = launch["rows"], launch["k"], launch["out"], launch["bl"]
    flops = 2 * 2.0 * m * k * n * bl
    nbytes = 4.0 * (n * bl * (m + k) + 2 * m * k)
    return flops, nbytes
