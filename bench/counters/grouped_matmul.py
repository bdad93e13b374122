"""Operations and bytes a grouped matmul needs: ``rows`` rows, each of one
of ``groups`` groups, times its group's (k, n) weights.  Every group's
weights are read once, the rows once, and the result written once,
whatever tiles implement it."""


def count(op: dict, itemsize: int) -> tuple:
    m, n, k, g = op["rows"], op["n"], op["k"], op["groups"]
    return 2.0 * m * n * k, float(itemsize * (m * k + g * k * n + m * n))
