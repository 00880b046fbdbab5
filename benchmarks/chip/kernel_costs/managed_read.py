"""Operations and bytes of one ``managed_read`` launch (the fused managed
read: noise management, both two-phase bound-management reads, select and
replica average in one kernel).

``rows`` vectors of length ``k`` against ``out`` output columns: one
(rows x k) x (k x out) product, which both reads share; f32 array, inputs,
outputs and the per-vector saturation flag once each.
"""


def cost(launch):
    n, k, m = launch["rows"], launch["k"], launch["out"]
    return 2.0 * n * k * m, 4.0 * (m * k + n * k + n * m + n)
