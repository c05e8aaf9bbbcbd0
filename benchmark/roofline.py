"""The table of peaks and the functions that compute a kernel's bytes and
operations from its shapes. Kept with the benchmark so that no PR that
claims a gain can change the yardstick. Each function says what the
ALGORITHM needs for one query, not what the current program moves, and
what it leaves out.
"""

from __future__ import annotations

# device_kind -> (peak FLOP/s, peak bytes/s). Source: Google Cloud
# documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM (the same
# table as telemetry/kernels.py DEVICE_PEAKS). A device that is not here
# is an error, not a default.
DEVICE_PEAKS = {
    "TPU v5 lite": (197e12, 819e9),
    "TPU v5e": (197e12, 819e9),
}


def peaks(device_kind: str):
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind "
                       f"[{device_kind}]: add it to DEVICE_PEAKS with its "
                       f"source") from None


def least_seconds(nbytes: float, flops: float, peak):
    """The least time the chip could take, and which peak bounds it."""
    t_flops, t_bytes = flops / peak[0], nbytes / peak[1]
    return (t_bytes, "memory") if t_bytes >= t_flops \
        else (t_flops, "compute")


def knn_exact(sizes: dict, work: dict):
    """Exact l2 k-NN, one query: read every padded f32 vector once, one
    multiply-add a component. Leaves out the norms vector (4 B a doc,
    under 1%), the [d_pad] distance vector's write and re-read for top-k
    (another ~1.6% at 128-d) and the query itself."""
    lanes = sizes["d_pad"] * sizes["dimension"]
    return 4.0 * lanes, 2.0 * lanes


def bm25_dense(sizes: dict, work: dict):
    """Dense BM25, one query: read 8 B a posting lane of the query's
    terms (doc id + tf) and 4 B of norm a lane; write, re-read for the
    scatter's result and read for top-k the [d_pad] f32 score vector
    (three passes). Leaves out the length table, idf/weights and the
    top-k network's own traffic. Operations: ~8 a lane (BM25 partial),
    far under the memory bound."""
    lanes = work["lanes"]
    return 12.0 * lanes + 12.0 * sizes["d_pad"], 8.0 * lanes


def bm25_candidate(sizes: dict, work: dict):
    """Candidate-buffer BM25, one query: 8 B a gathered posting lane and
    4 B of norm a lane; the sorted buffer stays on chip. Leaves out the
    sort's passes over the buffer (the algorithm's choice, not a need)
    and the block ids' upload. Operations: ~8 a lane."""
    lanes = work["lanes"]
    return 12.0 * lanes, 8.0 * lanes


KERNELS = {"knn": knn_exact, "bm25_dense": bm25_dense,
           "bm25_candidate": bm25_candidate}
