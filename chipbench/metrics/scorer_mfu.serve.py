"""The scorer's share of the chip's peak: the algorithm's FLOPs of
scoring the real (unpadded) rows against every sample of the bank
(``harness/work.py``) per scorer dispatch, times the dispatches per
second of the traced window, over the peak. Padding rows and warm-up
dispatches count as waste."""


def read(ctx):
    t = ctx["trace"]
    if t["top_module_runs"] <= 0 or t["window_s"] <= 0:
        return None
    rate = ctx["flops_per_unit"] * t["top_module_runs"] / t["window_s"]
    return 100.0 * rate / (ctx["chips"] * ctx["peak"]["flops"])
