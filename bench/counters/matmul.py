"""Operations and bytes a matmul needs: (m, k) @ (k, n), each operand read
once and the result written once, whatever tiles implement it."""


def count(op: dict, itemsize: int) -> tuple:
    m, n, k = op["m"], op["n"], op["k"]
    return 2.0 * m * n * k, float(itemsize * (m * k + k * n + m * n))
