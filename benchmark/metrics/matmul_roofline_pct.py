"""Tile kernels: the dot and convolution operations' share of their
roofline. For every such op in the trace (fused or not): its nominal
flops from the compiled HLO (``benchmark.hlo``) over the peak for its
operand type (``benchmark/peaks.json``) is the least time it could take;
the share is the sum of those least times over the ops' summed device
time. f32 products are held to the bf16 peak, so f32 at HIGHEST (six
bf16 passes) reads at most about a sixth."""
from benchmark import peaks


def read(ctx):
    t = ctx["trace"]
    ideal = busy = 0.0
    for evs in t.devices.values():
        for name, a, b in evs:
            info = t.info(name)
            if info["flops"] > 0:
                ideal += info["flops"] / peaks.product_peak(
                    ctx["device_kind"], info["operand"])
                busy += (b - a) * 1e-9
    if busy <= 0:
        return None
    return 100.0 * ideal / busy
