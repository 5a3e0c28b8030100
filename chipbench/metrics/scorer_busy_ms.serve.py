"""Compute-busy device time per scorer dispatch over the traced window:
the union of the non-collective ops' intervals divided by the runs of
the window's longest-running program, the batched scorer (``serve``'s
per-call bucket warm-up dispatches count as runs)."""


def read(ctx):
    t = ctx["trace"]
    if t["top_module_runs"] <= 0 or t["compute_s"] <= 0:
        return None
    return 1e3 * t["compute_s"] / t["top_module_runs"]
