"""Operations and bytes one Jacobi-PCG iteration of the P1 problem needs.

The counts belong to the algorithm, not to an implementation, so a
faster matvec is judged against the same work.  Per element, one matvec
streams the connectivity (4 int32) and the symmetric 4x4 element matrix
(10 float32) once: 56 bytes; its gathers and scatter-adds can stay on
chip.  Per vertex slot, the vector updates of one iteration, fused as far
as the data dependences allow, touch 13 float32 values: the dot p.Ap
reads p and Ap (2); x += a p, r -= a Ap and r.z with z = r / d read x, r,
p, Ap, d and write x, r (7); p = z + b p reads r, d, p and writes p (4).
Operations: the element apply is 16 multiplies and 12 adds; per vertex
slot two dots (4), two axpys (4), the preconditioner (1), the direction
update (2) and the residual norm (2).
"""
from __future__ import annotations

ELEMENT_BYTES = 4 * 4 + 10 * 4
VERTEX_BYTES = 13 * 4
ELEMENT_FLOPS = 16 + 12
VERTEX_FLOPS = 4 + 4 + 1 + 2 + 2


def pcg_iteration(n_elements: int, n_vertex_slots: int) -> tuple[int, int]:
    """(bytes, flops) of one PCG iteration over these elements and the
    vertex slots the parts hold (a shared vertex once per part)."""
    return (ELEMENT_BYTES * n_elements + VERTEX_BYTES * n_vertex_slots,
            ELEMENT_FLOPS * n_elements + VERTEX_FLOPS * n_vertex_slots)


def roofline_share(nbytes: float, flops: float, seconds: float,
                   peak: dict) -> float:
    """Least time the chip could take over the time taken, in percent."""
    least = max(nbytes / peak["hbm_bytes_per_s"],
                flops / peak["flops_per_s"])
    return 100.0 * least / seconds
