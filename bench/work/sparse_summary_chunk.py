"""Work one sparse chunk of the one-pass summary needs (arXiv:1610.06656
Alg. 1 step 1, with the held-out probe block), as functions of the real
nonzeros of A and of B: the sketches ``P^T A`` and ``P^T B`` (one
multiply-add of a k-vector per nonzero), the squared column norms and the
probe summand ``A^T (B Omega)``, reading each nonzero once as a column id
and a value, 8 bytes. The projection and the accumulators are not counted,
as in ``summary_chunk.py``: the projection is generated, not data, and how
the sums are held is the implementation's choice."""


def flops(nnz_a: int, nnz_b: int, k: int, probes: int) -> float:
    sketches = 2.0 * k * (nnz_a + nnz_b)
    norms = 2.0 * (nnz_a + nnz_b)
    probe = 2.0 * probes * (nnz_a + nnz_b)
    return sketches + norms + probe


def bytes_moved(nnz_a: int, nnz_b: int) -> float:
    return 8.0 * (nnz_a + nnz_b)
