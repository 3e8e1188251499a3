"""Share of the device buckets' rows that carry a query: queries the flush
sent to the runners (served less LRU hits and deduplicated tickets) over
``batches`` x bucket size, over the measured window."""


def read(record):
    a, b = record["stats_before"], record["stats_after"]
    batches = b["batches"] - a["batches"]
    if batches <= 0:
        return None
    ran = ((b["served"] - a["served"]) - (b["cache_hits"] - a["cache_hits"])
           - (b["deduped"] - a["deduped"]))
    return 100.0 * ran / (batches * record["batch"])
