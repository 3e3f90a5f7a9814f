"""Expert GLU over capacity-packed tokens, ``repro.kernels.expert_glu``:
y = (silu(x W_gate) * (x W_up)) W_down per expert."""
from __future__ import annotations

EVENT = "expert_glu"


def count(*, E: int, rows: int, d: int, F: int, dtype: str):
    """``rows`` is the number of (token, expert) assignments routed to
    the experts, not the padded capacity: three d x F contractions each;
    every expert's weights read once, the rows read and written once."""
    item = 4 if dtype == "float32" else 2
    flops = 6 * rows * d * F
    nbytes = item * (2 * rows * d + 3 * E * d * F)
    cls = "f32_highest" if dtype == "float32" else "bf16"
    return {cls: float(flops)}, float(nbytes)
