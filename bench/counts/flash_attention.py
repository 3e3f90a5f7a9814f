"""Causal or full softmax attention, ``repro.kernels.flash_attention``."""
from __future__ import annotations

EVENT = "flash_attention"


def pairs(Tq: int, Tk: int, causal: bool) -> int:
    """Query-key pairs the mask lets through; causal queries are the last
    ``Tq`` of ``Tk`` positions."""
    if not causal:
        return Tq * Tk
    # query i sees keys 0 .. Tk - Tq + i
    return Tq * (Tk - Tq) + Tq * (Tq + 1) // 2


def count(*, B: int, Tq: int, Tk: int, H: int, D: int, dtype: str,
          causal: bool = True, Hk: int | None = None):
    """q (B, Tq, H, D) against k, v (B, Tk, Hk, D): two contractions of D
    per admitted pair; q, k, v read and the output written once."""
    Hk = H if Hk is None else Hk
    item = 4 if dtype == "float32" else 2
    flops = 4 * B * H * D * pairs(Tq, Tk, causal)
    nbytes = item * (2 * B * Tq * H * D + 2 * B * Tk * Hk * D)
    cls = "f32_highest" if dtype == "float32" else "bf16"
    return {cls: float(flops)}, float(nbytes)
