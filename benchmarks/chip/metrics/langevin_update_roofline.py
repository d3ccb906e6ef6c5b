"""Roofline share of the fused Langevin update kernel: the least time its
bytes need (every parameter leaf read as x and g and written as x, in the
leaf's dtype, per commit and chain) at the chip's HBM bandwidth, over the
kernel's summed device time in the traced window, in percent."""

from chipbench import flops, traces

#: the kernel's call: a Pallas ``tpu_custom_call`` whose scalar-prefetch
#: operands are the int32 seed pair and the float32 (gamma, scale) pair
KERNEL = (r'custom-call\(s32\[2\]\{[^}]*\} [^,]+, f32\[2\]\{.*'
          r'custom_call_target="tpu_custom_call"')


def read(layer: dict):
    tr = layer.get("trace")
    if not tr or not layer.get("commits"):
        return None
    ns = traces.named_ns(tr, KERNEL)
    if ns <= 0:
        return None
    nbytes = layer["commits"] * layer["traffic"]["chains"] * \
        flops.langevin_bytes(layer["param_leaves"])
    least = flops.least_time_s(0.0, nbytes, layer["peak"])
    return 100.0 * least / (ns / 1e9)
