"""Share of the traversal's roofline: the least time the window's device
buckets need at the chip's peak HBM bandwidth (each bucket's algorithm,
bench/algorithms/<alg>.py, counts its least bytes; see bench/roofline.py)
over the device's busy time, summed over the chips used."""
from bench import spec


def read(record):
    if not record.get("busy_s") or not record.get("buckets"):
        return None
    least = sum(spec.algorithm(b["alg"]).least_bytes(
        record["n"], record["nnz"], b["rows"], b["frontier_entries"])
        for b in record["buckets"])
    seconds = least / record["peaks"]["hbm_bytes_per_s"]
    return 100.0 * seconds / (record["busy_s"] * record["chips"])
