"""Model FLOP utilisation of the sampling window: the forward and backward
FLOPs every committed sequence needs (causal attention included, nothing
counted twice for recomputation) over the window's host time, over chips
times the chip's peak, in percent."""

from chipbench import flops


def read(layer: dict):
    if not layer.get("commits"):
        return None
    tr = layer["traffic"]
    per_commit = tr["sequences_per_commit"] * tr["chains"] * \
        flops.train_flops_per_sequence(layer["conf"], tr["seq_len"])
    rate = layer["commits"] * per_commit / layer["window_s"]
    return 100.0 * rate / (layer["chips"] * layer["peak"]["bf16_flops_per_s"])
