"""Delayed-gradient sampling of a DeepSeek-V3-style model (latent attention,
a leading dense layer, sigmoid-routed experts of which this chip holds a
share) through ``ClusterEngine``.

The job, the window and the check are ``drivers/sample.py``'s, reused by
import: W-Con SGLD with the fused update, weights and token batches made on
the device from the seed, the first chunk's losses and parameters checked
against the plain reference after the window.  What differs:

- the program's configuration is built here from the configuration file
  (HF keys, its ``deployment`` and its ``score_correction_bias`` profile);
  ``harness.arch_config`` reads every file as a dense model, so the
  ``cfg`` a caller passes is looked up again by its name;
- the reference is ``chipbench/reference_mla_moe.py``, fed the same fixed
  routing bias;
- the held experts' routed work of the window (the program's counter
  ``moe.assignments_held``) goes to the readers as ``held_assignments``,
  and the first chunk's loads per layer, the program's and the
  reference's, are printed.
"""

from __future__ import annotations

import time

import numpy as np

from chipbench import harness, reference_mla_moe, traces
from chipbench.harness import Outcome, free_device_memory, memory_peak
from chipbench.weights import make_weights

sample = harness.driver({"driver": "sample"})
program_reading = sample.program_reading

#: configuration files by name: a caller's own (a cut for a test) stands in
#: for the manifest's
CONFS: dict = {}
HELD = "moe.assignments_held"


def bias_profile(conf: dict) -> tuple:
    """The fixed ``e_score_correction_bias`` rows, one per MoE layer over
    the router's outputs: ``hot`` on the layer's hot held expert, 0
    elsewhere; the same for every seed."""
    spec, dep = conf["score_correction_bias"], conf["deployment"]
    rows = []
    for layer in range(conf["num_hidden_layers"]
                       - conf["first_k_dense_replace"]):
        row = [0.0] * dep["router_outputs"]
        hot = spec["hot_expert_by_moe_layer"][layer]
        row[dep["first_expert"] + hot] = float(spec["hot"])
        rows.append(tuple(row))
    return tuple(rows)


def program_config(conf: dict):
    """The program's ArchConfig for a DeepSeek-V3 configuration file."""
    from repro.configs import ArchConfig

    for key, want in (("q_lora_rank", None), ("n_group", 1),
                      ("topk_group", 1), ("topk_method", "noaux_tc"),
                      ("moe_layer_freq", 1), ("norm_topk_prob", True)):
        if conf[key] != want:
            raise ValueError(f"{key}={conf[key]!r}: only {want!r} is run")
    dep = conf["deployment"]
    return ArchConfig(
        name=conf["name"], family="moe", source=conf["source"],
        num_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"],
        head_dim=conf["qk_nope_head_dim"] + conf["qk_rope_head_dim"],
        d_ff=conf["moe_intermediate_size"], vocab_size=conf["vocab_size"],
        rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]),
        tie_embeddings=conf["tie_word_embeddings"], act=conf["hidden_act"],
        dtype=conf["torch_dtype"],
        kv_lora_rank=conf["kv_lora_rank"],
        qk_nope_head_dim=conf["qk_nope_head_dim"],
        qk_rope_head_dim=conf["qk_rope_head_dim"],
        v_head_dim=conf["v_head_dim"],
        num_experts=dep["router_outputs"],
        experts_per_token=conf["num_experts_per_tok"],
        num_shared_experts=conf["n_shared_experts"], router_aux_coef=0.0,
        router_score=conf["scoring_func"],
        routed_scaling_factor=float(conf["routed_scaling_factor"]),
        score_correction_bias=bias_profile(conf),
        experts_held=conf["n_routed_experts"],
        expert_offset=dep["first_expert"],
        first_k_dense=conf["first_k_dense_replace"],
        dense_d_ff=conf["intermediate_size"], block_pattern=("attn_moe",))


def _conf(name: str) -> dict:
    return CONFS.get(name) or harness.config_file(harness.manifest(), name)


def as_program(cfg):
    """The program's configuration for ``cfg``, which may be the dense
    reading ``harness.arch_config`` made of the same file."""
    return cfg if cfg.kv_lora_rank else program_config(_conf(cfg.name))


class Sampling(sample.Sampling):
    """``sample.Sampling`` on the program's configuration, keeping the
    first chunk's held-expert loads."""

    def __init__(self, cfg, tr: dict, seed: int):
        super().__init__(as_program(cfg), tr, seed)

    def first_chunk(self):
        import jax

        batches = self.take[self.k](self.pool, 0)
        self.state, aux = self.engine.run(
            self.state, steps=self.k, schedule=self.first_sched,
            batches=batches)
        t = time.perf_counter()
        self.after = jax.device_get(self.state.params)
        self.batches = np.asarray(batches["tokens"])
        self.loads = np.asarray(aux["expert_tokens"])[:, 0]
        return np.asarray(aux["loss"]), time.perf_counter() - t


def reference_run(conf: dict, cfg, tr: dict, seed: int, batches, delays,
                  prec: str = "highest", view=None, zero_grad: bool = False):
    """``sample.reference_run`` with this model's reference."""
    import jax

    rows = batches[:, 0]
    if view is not None:
        rows = np.stack([view(b) for b in rows])
    key = jax.random.split(sample.chain_key(seed), tr["chains"])[0]
    x0 = make_weights(as_program(cfg), seed)
    return reference_mla_moe.sgld_commits(
        reference_mla_moe.MoEConfig.from_file(conf), prec, x0, rows,
        delays[:, 0], key, tr["gamma"], tr["sigma"], bias_profile(conf),
        depth=tr["tau"] + 1, zero_grad=zero_grad)


def compare(cfg, seed: int, losses, after, ref) -> dict:
    return sample.compare(as_program(cfg), seed, losses, after, ref)


def held_count() -> float:
    """The program's count of assignments routed to the held experts."""
    from repro.obs.metrics import registry

    return registry().counter(HELD).value


def _loads_note(name: str, loads) -> str:
    """Per MoE layer: the first chunk's busiest held expert over the mean
    held load, and the loads summed over its commits."""
    tot = np.asarray(loads).sum(axis=0)  # (layers, held)
    ratio = [round(float(r.max() / max(r.mean(), 1e-9)), 3) for r in tot]
    return (f"{name} first-chunk held loads by MoE layer {tot.tolist()}, "
            f"busiest over mean {ratio}")


def run(ctx) -> Outcome:
    import jax
    from jax.profiler import TraceAnnotation
    from repro.analysis.instrument import instrument

    tr = ctx.traffic
    CONFS[ctx.conf["name"]] = ctx.conf
    cfg = program_config(ctx.conf)
    marks = [("start", time.perf_counter())]
    job = Sampling(cfg, tr, ctx.seed)
    marks.append(("engine and state built", time.perf_counter()))
    first, capture_s = job.first_chunk()
    marks.append(("first chunk", time.perf_counter()))
    job.call()  # every host-side program of a window call, compiled here
    marks.append(("warm call", time.perf_counter()))
    tokens_per_commit = tr["sequences_per_commit"] * tr["seq_len"]
    with instrument() as rep, traces.recording(ctx.trace) as rec:
        with TraceAnnotation(traces.WINDOW):
            t0 = time.perf_counter()
            setup_s = t0 - ctx.t_start - capture_s
            held0 = held_count()
            losses = []
            while True:
                with TraceAnnotation("sample.run"):
                    losses.append(job.call())
                if time.perf_counter() - t0 >= ctx.seconds:
                    break
            window_s = time.perf_counter() - t0
            held = held_count() - held0
    losses = np.concatenate(losses)
    commits = losses.shape[0]
    peak = memory_peak(ctx.devices)
    after, batches, delays = job.after, job.batches, job.delays()
    loads = job.loads
    param_leaves = [(int(np.prod(a.shape[1:])), a.dtype.itemsize)
                    for a in jax.tree_util.tree_leaves(after)]
    del job
    free_device_memory()
    t_check = time.perf_counter()
    ref = reference_run(ctx.conf, cfg, tr, ctx.seed, batches, delays)
    nums = compare(cfg, ctx.seed, *program_reading(first, after), ref)
    check_s = time.perf_counter() - t_check
    limits = tr["limits"][ctx.conf["name"]]
    chips = len(ctx.devices)
    return Outcome(
        e2e={"sample_tokens_per_s":
             commits * tokens_per_commit / window_s / chips,
             "setup_s": setup_s},
        compared={k: (nums[k], limits[k]) for k in limits},
        attempted=int(losses.size),
        failed=int((~np.isfinite(losses)).sum()),
        memory_peak_bytes=peak, chips=chips,
        layer={"trace": rec.get("trace"), "commits": commits,
               "tokens": commits * tokens_per_commit, "window_s": window_s,
               "param_leaves": param_leaves, "held_assignments": held},
        notes=[f"compiles in window: {rep.xla_compiles} "
               f"(traces {rep.num_traces})",
               f"first chunk losses {nums['losses']}, reference "
               f"{nums['ref_losses']}, leaves left out of the change: "
               f"{nums['leaves_left_out']}",
               _loads_note("program", loads),
               _loads_note("reference", ref.loads),
               f"window: {commits} commits in {window_s:.3f} s, "
               f"{held:.0f} held assignments; check capture "
               f"{capture_s:.3f} s kept out of setup_s; reference check "
               f"{check_s:.3f} s",
               "set-up: " + ", ".join(f"{k} at {t - ctx.t_start:.2f} s"
                                      for k, t in marks)])
