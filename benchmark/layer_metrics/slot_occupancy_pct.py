"""Share of the decode slots that held a request, over the window: the
time from each request's first token to its last, summed over requests
and clipped to the window, over slots x window (client's records)."""


def read(ctx):
    res = ctx["res"]
    if "busy_slot_seconds" not in res:
        return None
    return 100.0 * res["busy_slot_seconds"] / (
        res["slots"] * (res["window"][1] - res["window"][0]))
