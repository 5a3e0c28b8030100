"""Device time per hybrid iteration inside collectives while no compute
op runs on that chip: the part of ``allreduce_ms.fit`` that is waiting
or transfer, not overlapped. ``None`` where the trace holds no
collective."""


def read(ctx):
    t = ctx["trace"]
    if t["top_module_runs"] <= 0 or t["collective_s"] <= 0:
        return None
    return 1e3 * t["collective_alone_s"] / t["top_module_runs"]
