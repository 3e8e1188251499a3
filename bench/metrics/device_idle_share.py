"""Share of the time with a query outstanding (sent, not yet answered) in
which no operation ran on the device: what the host, the scheduler's
window and the flush hold the chip back while work waits. Time with no
query outstanding is left out, since an open loop below capacity leaves
the device idle there by design. Busy time is the union of the device-op
intervals, averaged over the chips used."""


def read(record):
    if not record.get("pending_s"):
        return None
    return 100.0 * (1.0 - record["busy_pending_s"] / record["pending_s"])
