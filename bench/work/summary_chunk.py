"""Work one chunk of the one-pass summary needs (arXiv:1610.06656 Alg. 1
step 1, with the held-out probe block): the sketches ``Pi A`` and ``Pi B``,
the squared column norms and the probe summand ``A^T (B Omega)``, reading
A and B once. The projection is not counted: it is generated, not data, and
how it is made is the implementation's choice."""


def flops(rows: int, n1: int, n2: int, k: int, probes: int) -> float:
    sketches = 2.0 * k * rows * (n1 + n2)
    norms = 2.0 * rows * (n1 + n2)
    probe = 2.0 * rows * n2 * probes + 2.0 * rows * n1 * probes
    return sketches + norms + probe


def bytes_moved(rows: int, n1: int, n2: int, k: int, probes: int) -> float:
    return 4.0 * rows * (n1 + n2)
