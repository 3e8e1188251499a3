"""Share of the flush's LRU probes that hit, over the measured window."""


def read(record):
    a, b = record["stats_before"], record["stats_after"]
    lookups = b["lookups"] - a["lookups"]
    if lookups <= 0:
        return None
    return 100.0 * (b["hits"] - a["hits"]) / lookups
