"""Share of the traced window in which no compute op (any op but a
collective) ran on the device; the mean over the chips used."""


def read(ctx):
    t = ctx["trace"]
    if t["devices"] == 0 or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["compute_s"] / t["window_s"])
