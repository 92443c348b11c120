"""Published peaks, keyed by the ``device_kind`` JAX reports, and the
operation/byte counts of the kernels whose roofline share is reported.
A device that is not in the table is an error, not a default."""

from __future__ import annotations

#: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM
PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                    "source": "Google Cloud documentation, TPU v5e"},
    "TPU v5e": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                "source": "Google Cloud documentation, TPU v5e"},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add it to benchmarks/peaks.py "
                       "with its source") from None


def pairdist_counts(rows: int, archive_rows: int, failure_rows: int,
                    k: int, operand_bytes: int = 2) -> dict:
    """What one call of the pair min-distance kernel
    (``min_sq_distance_pair_pallas``) needs: for ``rows`` feature
    vectors of width ``k`` against ``archive_rows + failure_rows``
    centres, the cross products (2 flops per multiply-add), and each
    operand, each squared norm and each of the two outputs moved once
    (operands in the matmul type, norms and outputs float32)."""
    centres = archive_rows + failure_rows
    return {
        "flops": 2.0 * rows * centres * k,
        "bytes": float(operand_bytes * k * (rows + centres)
                       + 4 * (rows + centres) + 2 * 4 * rows),
    }


def pairdist_counts_of(shape: dict) -> dict:
    """``pairdist_counts`` from a run's shape (``run.py``'s ``shape``
    observation): each chip scores its shard of the population against
    every reference trace in one call."""
    return pairdist_counts(
        rows=shape["population_per_chip"] * shape["reference_traces"],
        archive_rows=shape["archive_rows"],
        failure_rows=shape["failure_rows"], k=shape["feature_pairs"])


def roofline_share(counts: dict, calls: int, kernel_s: float,
                   device_kind: str) -> dict:
    """Least time the chip could take for ``calls`` calls over the time
    they took, in percent, and which peak bounds it."""
    pk = peaks_for(device_kind)
    t_flops = counts["flops"] / pk["flops_per_s"]
    t_bytes = counts["bytes"] / pk["bytes_per_s"]
    least = max(t_flops, t_bytes) * calls
    return {"share_pct": 100.0 * least / kernel_s,
            "bound": "flops" if t_flops >= t_bytes else "bytes",
            "least_s": least}
