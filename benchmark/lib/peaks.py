"""Published peaks of the chips the benchmark may run on, keyed by a
substring of ``device_kind`` (first match wins, so "v5 lite" precedes "v5").
A device that is not here is an error, never a default: a share of an
assumed peak is not a measurement."""

from __future__ import annotations

PEAKS = (
    # needle, bf16 FLOP/s, HBM bytes/s, HBM bytes, source
    ("v5 lite", 197e12, 819e9, 16e9,
     "Google Cloud TPU documentation, 'TPU v5e': 197 TFLOP/s bf16, "
     "16 GB HBM2e at 819 GB/s per chip"),
    ("v5e", 197e12, 819e9, 16e9,
     "Google Cloud TPU documentation, 'TPU v5e' (same chip, other name)"),
)


def peaks_for(device_kind: str) -> dict:
    kind = (device_kind or "").lower()
    for needle, flops, bw, hbm, source in PEAKS:
        if needle in kind:
            return {"flops_bf16": flops, "hbm_bytes_per_s": bw,
                    "hbm_bytes": hbm, "source": source}
    raise ValueError(f"no published peaks for device_kind {device_kind!r}: "
                     f"add it to benchmark/lib/peaks.py with its source")
