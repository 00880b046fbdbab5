"""Operations and bytes of one ``noisy_read`` launch (one raw physical
read, as each iterative bound-management trial makes it).

``rows`` vectors of length ``k`` against ``out`` physical rows: one
(rows x k) x (k x out) product; f32 array, inputs and outputs once each.
"""


def cost(launch):
    n, k, m = launch["rows"], launch["k"], launch["out"]
    return 2.0 * n * k * m, 4.0 * (m * k + n * k + n * m)
