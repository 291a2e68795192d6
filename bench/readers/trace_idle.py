"""The device's idle share of the traced window, in %: for each chip 1 - (the
union of the intervals in which an operation ran) / traced window, and the
mean over the cell's chips."""


def read(params, facts):
    trace = facts.get("trace")
    if not trace or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
