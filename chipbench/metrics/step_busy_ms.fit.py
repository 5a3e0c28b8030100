"""Compute-busy device time per hybrid iteration: the union of the
non-collective ops' intervals over the traced window, divided by the
runs of the window's longest-running program, the hybrid step (mean
over the chips used)."""


def read(ctx):
    t = ctx["trace"]
    if t["top_module_runs"] <= 0 or t["compute_s"] <= 0:
        return None
    return 1e3 * t["compute_s"] / t["top_module_runs"]
