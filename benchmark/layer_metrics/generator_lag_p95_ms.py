"""How late the load generator sent: 95th percentile of actual send time
less due time, on the generator's own clock. A starved generator must
not be read as a fast server."""


def read(ctx):
    return ctx["res"].get("generator_lag_p95_ms")
