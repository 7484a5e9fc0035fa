"""Operations and bytes that the contraction layer's work requires.

Counted from the cell's shapes and its stated algorithm parameters alone,
never from the kernels the program happens to run: a later change that
fuses passes, changes precision or packs the mask is measured against the
same work.

One round of (D)CF-PCA runs, on every client block of ``m x nb`` entries,
``local_iters`` local iterations of ``inner_sweeps`` inner sweeps plus one
U-step.  Each sweep and each U-step needs the residual ``M - U V^T``
clipped to ``+-lam`` and one skinny contraction of it (``Psi^T U`` or
``Psi V``): ``4 m nb r`` operations and one read of the data block, of the
two factors, and one write of the contraction.  Each of those passes
depends on the one before, so the exact algorithm cannot share a read of
``M`` between them.  A solve ends with one residual-shrink pass, which
reads the block and writes ``S``.

Bytes: the data plane at 4 bytes an entry (the configurations state f32
data), the observation mask at 1 bit an entry (the least any exact layout
needs), the factors and outputs at 4 bytes.
"""
from __future__ import annotations

from dataclasses import dataclass

F32 = 4


@dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes)

    def scale(self, k: float) -> "Work":
        return Work(self.flops * k, self.bytes * k)


def contraction_pass(m: int, nb: int, rank: int, *, masked: bool,
                     out_rows: int) -> Work:
    """One residual-clip-contract pass over an ``m x nb`` block."""
    plane = m * nb * F32 + (m * nb / 8 if masked else 0.0)
    factors = (m + nb) * rank * F32
    return Work(4.0 * m * nb * rank, plane + factors + out_rows * rank * F32)


def shrink_pass(m: int, nb: int, rank: int, *, masked: bool) -> Work:
    """The final ``S = shrink(M - U V^T)`` pass over an ``m x nb`` block."""
    plane = m * nb * F32 + (m * nb / 8 if masked else 0.0)
    return Work(2.0 * m * nb * rank,
                plane + (m + nb) * rank * F32 + m * nb * F32)


def solve_work(m: int, n: int, rank: int, *, blocks: int, rounds: int,
               local_iters: int, inner_sweeps: int, masked: bool,
               solves: int = 1) -> Work:
    """Contraction-layer work of ``solves`` solves of ``rounds`` rounds on
    an ``m x n`` problem split into ``blocks`` equal column blocks."""
    nb = n // blocks
    sweep = contraction_pass(m, nb, rank, masked=masked, out_rows=nb)
    ustep = contraction_pass(m, nb, rank, masked=masked, out_rows=m)
    per_round = (sweep.scale(inner_sweeps) + ustep).scale(local_iters)
    per_solve = per_round.scale(rounds) + shrink_pass(m, nb, rank,
                                                      masked=masked)
    return per_solve.scale(blocks * solves)


def least_time(work: Work, flops_peak: float, bw_peak: float):
    """``(seconds, bound)``: the larger of operations over peak FLOP/s and
    bytes over peak bandwidth, and which of the two it is."""
    t_c = work.flops / flops_peak
    t_m = work.bytes / bw_peak
    return (t_m, "memory") if t_m >= t_c else (t_c, "compute")
