"""95th percentile of how late the load generator submitted a request
after it fell due. Generator and server share one thread, so this
includes the wait behind the step in flight."""


def read(ctx):
    return ctx["counts"].get("gen_lag_ms_p95")
