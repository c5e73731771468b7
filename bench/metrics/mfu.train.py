"""Model FLOPs of the steps in the traced window (``bench/flops.py``:
6 x (non-embedding + head parameters) + attention, per token) over the
window, as a share of the cell's chips' bf16 peak."""


def read(r):
    if not r.counters.get("flops"):
        return None
    return 100.0 * r.counters["flops"] / r.window_s / (
        r.chips * r.peak["bf16_flops"])
