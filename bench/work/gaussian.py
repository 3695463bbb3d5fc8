"""Work of the streamed Gaussian sketch pass (``kernels/gaussian_gram.py``).

What the algorithm needs for one problem: S·A with S an m_max × n Gaussian
matrix, 2·m_max·n·d operations, and the least bytes are A read once plus
the m_max × d sketch written once. Generating S is not counted.
"""

# Pallas custom calls whose instruction name holds one of these: the
# wrapper that calls the kernel, or the kernel itself once it is named
KERNELS = ("gaussian_sa", "_gauss_sa_kernel")


def flops(n: int, d: int, m_max: int) -> float:
    return 2.0 * m_max * n * d


def min_bytes(n: int, d: int, m_max: int, itemsize: int = 4) -> float:
    return float(itemsize) * (n * d + m_max * d)
