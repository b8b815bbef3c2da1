"""Device milliseconds per publication spent in the simplex solve.

Read where the solve is a program of its own: ``jit_propose_dag`` in the
trace's module line.  Inside ``ServiceLoop``'s tick the solve is a branch of
the tick program, and no name in the trace tells its operations apart.
"""
MODULE = "jit_propose_dag("


def read(ctx):
    red = ctx.get("trace")
    if not red or not ctx.get("pubs"):
        return None
    s = sum(d for name, _, d in red["modules"] if name.startswith(MODULE))
    return 1e3 * s / ctx["pubs"] if s > 0 else None
