"""Share of the serving window in which no op ran on the device, from the
trace: 1 - busy / window, busy being the union of the op intervals."""


def read(ctx):
    t = ctx["trace"]
    if not t or t["devices"] == 0 or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
