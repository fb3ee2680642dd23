"""Output tokens that reached the clients inside the window, per second
of window, in a cell offered more than the server sustains. Recorded, not
judged: about one run in six loses 8-23 % of its window to one stall of
the whole server (PERF.md, Open questions), which no bound of at most
10 % holds; the traced run's value has the profiler's slower host loop
in it."""


def read(ctx):
    return ctx["res"].get("serve_tokens_per_s")
