"""Hits of the `ec_interval` cache as a share of its look-ups in the
window, in per cent."""


def read(obs, cell):
    hits = obs.counters.get("cache_hits")
    misses = obs.counters.get("cache_misses")
    if hits is None or misses is None or hits + misses == 0:
        return None
    return 100.0 * hits / (hits + misses)
