"""Tickets the scheduler hands each window: the mean of the server's
``window_occupancy`` histogram over the measured window, times the bucket
size (occupancy is tickets / batch)."""


def read(record):
    h0 = record["stats_before"]["window_occupancy"]
    h1 = record["stats_after"]["window_occupancy"]
    n0, n1 = h0.get("count", 0), h1.get("count", 0)
    if n1 <= n0:
        return None
    total = h1["mean"] * n1 - (h0["mean"] * n0 if n0 else 0.0)
    return total / (n1 - n0) * record["batch"]
