"""Model FLOP utilisation of an MoE sampling window: the forward and
backward FLOPs every committed sequence needs (latent attention, causal,
the dense layers, routers, shared experts and head by shape, and the
routed experts by the held assignments the program counted in the window;
nothing counted twice for recomputation) over the window's host time, over
chips times the chip's peak, in percent."""

from chipbench import flops_mla_moe


def read(layer: dict):
    if not layer.get("commits") or layer.get("held_assignments") is None:
        return None
    tr = layer["traffic"]
    sequences = layer["commits"] * tr["chains"] * tr["sequences_per_commit"]
    total = flops_mla_moe.train_flops(layer["conf"], sequences,
                                      tr["seq_len"],
                                      layer["held_assignments"])
    rate = total / layer["window_s"]
    return 100.0 * rate / (layer["chips"] * layer["peak"]["bf16_flops_per_s"])
