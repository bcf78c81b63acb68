"""Peaks of the chips this benchmark knows, and the work of one signature.

Peaks: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
197 TFLOP/s in bf16, 819 GB/s of HBM bandwidth, 16 GB of HBM, per chip.  A
device that is not in the table is an error, not a default.

Work per signature verified: PERF.md section 5 (builder's count, BASELINE.md
round 7, ``consensus_tpu/ops/limbs.py::measure_field_ops`` at batch 512): the
strict Ed25519 verifier does 2,738.9 field-multiplication equivalents per
signature, and one field multiplication is about 1,024 f32 multiply-adds plus
about 2,000 reduction operations, so about 4,048 floating-point operations.
The work is counted per signature VERIFIED — not per padded lane and not from
the compiled graph — so the share reads the same whatever implements the
kernel, and padding shows as a lower share.  Bytes per signature are the
kernel's inputs (a 64-byte signature, a 32-byte key and a 64-byte digest
scalar, with limb packing about 1 KiB in all): far under the operations'
time at these peaks, so the operations' peak is the one that bounds it.
"""

_V5E = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
#: device_kind as jax reports it -> peaks of one chip
PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}

FIELD_MUL_EQUIV_PER_SIGNATURE = 2738.9
FLOPS_PER_FIELD_MUL = 2 * 1024 + 2000
FLOPS_PER_SIGNATURE = FIELD_MUL_EQUIV_PER_SIGNATURE * FLOPS_PER_FIELD_MUL
BYTES_PER_SIGNATURE = 1024.0


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks known for device kind {device_kind!r}: add it to "
            "served_bench/peaks.py with its source"
        ) from None


def least_seconds(signatures: int, device_kind: str) -> tuple:
    """The least time the chip could take to verify ``signatures``, and which
    peak bounds it (``"flops"`` or ``"bytes"``)."""
    peaks = peaks_for(device_kind)
    by_flops = signatures * FLOPS_PER_SIGNATURE / peaks["flops_per_s"]
    by_bytes = signatures * BYTES_PER_SIGNATURE / peaks["hbm_bytes_per_s"]
    return (by_flops, "flops") if by_flops >= by_bytes else (by_bytes, "bytes")
