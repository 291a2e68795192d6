"""Mean device time of one compiled program for each of its runs, from the
device trace: the ``XLA Modules`` events whose name matches ``pattern``
(the jitted function's module name: no ``jax.named_scope`` tells programs
apart, ROADMAP D7), in milliseconds."""
import re


def read(params, facts):
    trace = facts.get("trace")
    if not trace:
        return None
    count = seconds = 0
    for name, (c, s) in trace["modules"].items():
        if re.search(params["pattern"], name):
            count, seconds = count + c, seconds + s
    if not count:
        return None
    return 1e3 * seconds / count
