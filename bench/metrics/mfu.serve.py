"""The traced window's work at the chip's peaks over the window: for each
step, the larger of its FLOPs over peak FLOP/s and its bytes (every weight
once, the K/V of the live positions read and of the new ones written)
over peak HBM bandwidth (``bench/flops.py``), summed, over window time."""


def read(r):
    costs = r.counters.get("serve_costs")
    if not costs:
        return None
    least = sum(max(f / r.peak["bf16_flops"], b / r.peak["hbm_bytes_per_s"])
                for f, b in costs)
    return 100.0 * least / (r.window_s * r.chips)
