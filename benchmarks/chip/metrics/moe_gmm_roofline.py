"""Roofline share of the MoE layers' grouped matmul: the least time the
kernel calls of the traced window need (each call multiplies its routed
rows through one hidden x expert-width matrix; bfloat16 rows, results and
the held experts' matrices each moved once) at the chip's peaks, over the
calls' summed device time, in percent.

The calls are the megablox ``gmm`` (forward and input gradient) and
``tgmm`` (weight gradient) Pallas kernels: a ``tpu_custom_call`` whose
operands open with the int32 scalar count of row tiles (the grid's dynamic
length), three int32 group-metadata vectors and the int32[1] group
offset.  The rows of a call are the window's held assignments (the
program's ``moe.assignments_held``) over its commits and MoE layers: every
(commit, layer) makes the same calls over the same rows."""

from chipbench import flops, flops_mla_moe, traces

KERNEL = (r'custom-call\(s32\[\]\{[^}]*\} [^,]+, '
          r'(s32\[\d+\]\{[^}]*\} [^,]+, ){3}s32\[1\]\{[^}]*\} .*'
          r'custom_call_target="tpu_custom_call"')


def read(layer: dict):
    tr = layer.get("trace")
    if not tr or not layer.get("commits") or not layer.get(
            "held_assignments"):
        return None
    ns = traces.named_ns(tr, KERNEL)
    calls = traces.named_count(tr, KERNEL)
    if ns <= 0 or not calls:
        return None
    conf = layer["conf"]
    rows = layer["held_assignments"] / (
        layer["commits"] * flops_mla_moe.moe_layers(conf))
    op, nbytes = flops_mla_moe.gmm_call(conf, rows)
    least = flops.least_time_s(calls * op, calls * nbytes, layer["peak"])
    return 100.0 * least / (ns / 1e9)
