"""Published chip peaks, keyed by ``device_kind``, and the work a kernel
call needs, counted from its shapes.

Source of the peaks: Google Cloud documentation, "TPU v5e" (system
architecture page): 197 TFLOP/s bf16, 393 TOP/s int8, 819 GB/s of HBM
bandwidth and 16 GB of HBM per chip.  JAX reports a v5e chip's
``device_kind`` as "TPU v5 lite".  A kind that is not in the table is an
error, not a default.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to benchmarks/chip/"
                       f"peaks.py with their source") from None


#: The Pallas query kernel's operands: hub, dist and count rows of both
#: sides, [L, B] each, 4 bytes an element (int32 / float32).
SPC_QUERY_OPERANDS = 6


def spc_query_bytes(batch: int, l_cap: int) -> int:
    """HBM bytes one ``spc_query`` call must move: six [L, B] 4-byte
    operands in, one int32 and one float32 per pair out.  ``batch`` is the
    padded pair count the kernel is launched on."""
    return SPC_QUERY_OPERANDS * l_cap * batch * 4 + 8 * batch


def spc_query_compares(batch: int, l_cap: int) -> int:
    """Vector compares one call makes: every s-side label against every
    t-side label of each pair.  These run on the VPU, which has no
    published peak, so they bound nothing here (PERF.md, Open
    questions)."""
    return l_cap * l_cap * batch


def spc_query_roofline(calls, seconds: float, device_kind: str):
    """Share (%) of its bytes roofline that the kernel reached: the least
    time its bytes need at the chip's HBM bandwidth, over the kernel's
    device time.  ``calls`` is an iterable of ``(batch, l_cap)``, one per
    kernel launch in the traced window.  None when nothing ran."""
    if seconds <= 0:
        return None
    total = sum(spc_query_bytes(b, l) for b, l in calls)
    if total <= 0:
        return None
    bw = peaks_for(device_kind)["hbm_bytes_per_s"]
    return 100.0 * (total / bw) / seconds
