"""``memory_stats()["peak_bytes_in_use"]`` of the fullest chip after the
window, in GiB."""


def read(params, facts):
    peak = facts.get("memory_peak_bytes")
    if not peak:
        return None
    return peak / 2**30
