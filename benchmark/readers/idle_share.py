"""Share of the traced window in which no operation ran on the device."""


def read(ctx, params):
    s = ctx["summary"]
    if not s or not s["window_s"]:
        return None
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])
