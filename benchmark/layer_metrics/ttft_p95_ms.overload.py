"""95th percentile of time to first token, from the instant the request
was due, in a cell offered more than the server sustains: the queue grows
all through the window, so this tail is recorded, not judged."""


def read(ctx):
    return ctx["res"].get("ttft_p95_ms")
