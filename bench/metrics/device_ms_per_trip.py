"""Device time per loop trip: the device's busy time over the window, in
ms, divided by the loop trips of the device buckets answered inside the
traced window (each bucket's trips are its deepest row's, from the
payloads' ``iterations``). Busy time also holds any bucket still running
at the window's close, so the reading errs high, never low."""


def read(record):
    trips = sum(b["trips"] for b in record.get("buckets") or [])
    if not record.get("busy_s") or trips <= 0:
        return None
    return 1000.0 * record["busy_s"] / trips
