"""1 - (union of the device's leaf-operation intervals) / (traced window),
averaged over the chips: the share of the window in which nothing ran."""


def read(ctx):
    trace = ctx["trace"]
    if trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
