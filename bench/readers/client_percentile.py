"""A percentile of a list of seconds that the load generator took on its
own clock (``lateness_s``: how late each request left; ``ttft_s``: first
token minus due, of all requests; ``gaps_s``: all gaps between streamed
tokens), in milliseconds."""
from bench.traffic.open_loop_http import percentile


def read(params, facts):
    values = facts.get(params["series"])
    if not values:
        return None
    return 1e3 * percentile(values, params["q"])
