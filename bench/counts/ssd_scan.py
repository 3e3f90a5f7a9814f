"""Chunked SSD scan, ``repro.kernels.ssd_scan``: y_t = c_t . S_t with
S_t = exp(log_a_t) S_{t-1} + b_t v_t^T."""
from __future__ import annotations

EVENT = "ssd_scan"


def count(*, B: int, T: int, H: int, N: int, P: int, chunk: int,
          dtype: str):
    """The chunked algorithm of Mamba-2 at the kernel's chunk: per chunk
    and head, the causal half of c b^T (N per pair) and of its product
    with v (P per pair), the chunk's state b^T v and the carried state's
    output c S (N P per position each), and the carry (N P).  The
    kernel also forms its decays as C x C x C matmuls over exact spans;
    the algorithm needs only a cumulative sum there, so those are not
    counted.  The kernel contracts every operand in f32 at HIGHEST,
    whatever its input dtype."""
    C = min(chunk, T)
    nc = -(-T // C)
    per_chunk = 2 * (N + P) * C * (C + 1) // 2 + 4 * C * N * P + 2 * N * P
    flops = B * H * nc * per_chunk
    item = 4 if dtype == "float32" else 2
    nbytes = (item * B * T * H * (2 * N + 2 * P)   # c, b, v in; y out
              + 4 * B * T * H                      # decay gates, f32
              + 4 * B * H * N * P)                 # final state, f32
    return {"f32_highest": float(flops)}, float(nbytes)
