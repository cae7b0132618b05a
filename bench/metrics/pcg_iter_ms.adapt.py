"""Device milliseconds per PCG iteration: the device time of the owned
distributed solve (the program ``jit_solve`` of
``fem.parallel.sharded_solve_dirichlet``: set-up, PCG loop and lifting)
over the CG iterations of the traced steps."""

PROGRAM = "jit_solve"


def read(rec):
    t, steps = rec.get("trace"), rec.get("steps")
    if not t or not steps or PROGRAM not in t["module_s"]:
        return None
    return 1e3 * t["module_s"][PROGRAM] / sum(s["cg_iters"] for s in steps)
