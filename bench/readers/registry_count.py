"""A sum of the program's own counters over the window (``registry_ratio``
without a denominator): a count that should read 0 is reported as it is."""
from bench.readers.registry_ratio import total


def read(params, facts):
    registry = facts.get("registry")
    if registry is None:
        return None
    return total(registry, params["terms"])
