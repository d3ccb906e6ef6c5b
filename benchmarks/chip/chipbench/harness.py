"""Manifest, configurations, traffic mixes and readers, found by name.

``BENCHMARK.json`` at the checkout root names every cell.  A cell names a
configuration (``configs/<config>.json``, the file the manifest gives) and a
traffic mix (``traffic/<traffic>.json``); the mix names its driver
(``drivers/<driver>.py``), and every per-layer metric is read by
``metrics/<name>.py``.  Adding one of them is adding files and manifest
entries: nothing here lists them.
"""

from __future__ import annotations

import importlib.util
import json
import math
import os
from dataclasses import dataclass, field
from typing import Any

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(BENCH))
#: JAX's persistent compilation cache: a fixed path inside the checkout, so
#: only a cell's first run in a checkout compiles
CACHE_DIR = os.path.join(ROOT, ".jax_cache", "chipbench")

#: keys of a configuration file that map onto the program's ArchConfig
_ARCH_KEYS = {
    "num_hidden_layers": "num_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "qk_norm": "qk_norm",
    "hidden_act": "act",
    "torch_dtype": "dtype",
}


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def manifest(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def cell(man: dict, name: str) -> dict:
    for w in man["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config_file(man: dict, name: str) -> dict:
    for c in man["configs"]:
        if c["name"] == name:
            return load_json(os.path.join(ROOT, c["file"]))
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def traffic_file(name: str) -> dict:
    return load_json(os.path.join(BENCH, "traffic", f"{name}.json"))


def _load(path: str, label: str):
    spec = importlib.util.spec_from_file_location(label, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver(traffic: dict):
    """The driver module a traffic mix names."""
    name = traffic["driver"]
    return _load(os.path.join(BENCH, "drivers", f"{name}.py"),
                 f"chipbench_driver_{name}")


def reader(metric: str):
    """The reader module of one per-layer metric."""
    return _load(os.path.join(BENCH, "metrics", f"{metric}.py"),
                 f"chipbench_metric_{metric.replace('.', '_')}")


def cell_metrics(man: dict, workload: str, kind: str) -> list:
    """The ``kind`` (``end_to_end`` or ``per_layer``) metrics a cell
    reports: those that list it, and those that list no cells, where the
    cell reports what they move."""
    e2e = [m for m in man["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    if kind == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in man["per_layer"]
            if workload in m.get("workloads", ())
            or ("workloads" not in m and m["moves"] in names)]


def peaks(kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; an unknown kind is
    an error, never a default."""
    table = load_json(os.path.join(BENCH, "peaks.json"))
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def arch_config(conf: dict):
    """The program's ArchConfig for a configuration file."""
    from repro.configs import ArchConfig

    kw = {dst: conf[src] for src, dst in _ARCH_KEYS.items() if src in conf}
    return ArchConfig(name=conf["name"], family="dense",
                      source=conf["source"], **kw)


def base_key(seed: int):
    """The run's root PRNG key: every bit of a seed above 32 bits counts."""
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


@dataclass
class Outcome:
    """What a driver hands back to ``run.py``."""

    e2e: dict                       # end-to-end metric name -> value
    compared: dict                  # short name -> (value, limit)
    attempted: int
    failed: int
    memory_peak_bytes: int
    chips: int
    layer: dict = field(default_factory=dict)  # inputs of the readers
    notes: list = field(default_factory=list)  # earlier output lines

    @property
    def correct(self) -> bool:
        return self.failed == 0 and all(
            v is not None and math.isfinite(v) and v <= lim
            for v, lim in self.compared.values())


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest device (0 where the backend keeps
    no memory statistics, as the CPU does)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def free_device_memory() -> None:
    """Drop compiled programs' and arrays' last references before the
    reference runs on the same chip."""
    import gc

    import jax

    gc.collect()
    jax.clear_caches()
    gc.collect()
