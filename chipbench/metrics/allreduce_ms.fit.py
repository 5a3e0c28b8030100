"""Device time inside collective ops (the master sync's all-reduces)
per hybrid iteration, mean over the chips used; a chip's wait for the
slowest one counts. ``None`` where the trace holds no collective."""


def read(ctx):
    t = ctx["trace"]
    if t["top_module_runs"] <= 0 or t["collective_s"] <= 0:
        return None
    return 1e3 * t["collective_s"] / t["top_module_runs"]
