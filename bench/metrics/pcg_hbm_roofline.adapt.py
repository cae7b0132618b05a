"""Share of the HBM roofline the PCG solve reaches: the least time one
chip needs for the iterations' algorithmic bytes and operations
(``bench/counts.py``, from each solved mesh's elements and vertices, an
even share per chip) over the device time of the solve program
(``jit_solve``).  Bytes bound it at every size the cells run."""
from bench import counts

PROGRAM = "jit_solve"


def read(rec):
    t, steps, peak = rec.get("trace"), rec.get("steps"), rec.get("peak")
    if not t or not steps or not peak or PROGRAM not in t["module_s"]:
        return None
    nbytes = flops = 0.0
    for s in steps:
        b, f = counts.pcg_iteration(s["n_tets"], s["n_verts"])
        nbytes += b * s["cg_iters"] / rec["parts"]
        flops += f * s["cg_iters"] / rec["parts"]
    return counts.roofline_share(nbytes, flops, t["module_s"][PROGRAM], peak)
