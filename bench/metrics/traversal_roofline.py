"""Share of the traversal's roofline: the least time the window's device
buckets need at the chip's peak HBM bandwidth (bench/roofline.py counts
their least bytes from the algorithm) over the device's busy time,
summed over the chips used."""
from bench import roofline


def read(record):
    if not record.get("busy_s") or not record.get("buckets"):
        return None
    least = sum(roofline.least_bytes(b["alg"], record["n"], record["nnz"],
                                     b["rows"], b["frontier_entries"])
                for b in record["buckets"])
    seconds = least / record["peaks"]["hbm_bytes_per_s"]
    return 100.0 * seconds / (record["busy_s"] * record["chips"])
