"""The hybrid iteration's share of the chips' peak: the algorithm's
FLOPs per iteration (``harness/work.py``, from the configuration's
shapes) times the step's runs per second of the traced window, over
chips x peak."""


def read(ctx):
    t = ctx["trace"]
    if t["top_module_runs"] <= 0 or t["window_s"] <= 0:
        return None
    rate = ctx["flops_per_unit"] * t["top_module_runs"] / t["window_s"]
    return 100.0 * rate / (ctx["chips"] * ctx["peak"]["flops"])
