"""Find a cell's files by the names in ``BENCHMARK.json`` and build the
program's ``ModelConfig`` from a configuration file."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return _load(os.path.join(ROOT, "BENCHMARK.json"))


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict       # bench/configs/<config>.json
    traffic: dict      # bench/traffic/<traffic>.json
    limits: dict       # bench/limits/<workload>.json ({} when absent)
    end_to_end: tuple  # the BENCHMARK.json entries this cell reports
    per_layer: tuple


def _reports(metric: dict, workload: str, reported: set) -> bool:
    if "workloads" in metric:
        return workload in metric["workloads"]
    return metric.get("moves") is None or metric["moves"] in reported


def cell(name: str, bench: dict | None = None) -> Cell:
    bench = bench or benchmark()
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(by_name)}")
    w = by_name[name]
    config = _load(os.path.join(HERE, "configs", w["config"] + ".json"))
    traffic = _load(os.path.join(HERE, "traffic", w["traffic"] + ".json"))
    lpath = os.path.join(HERE, "limits", name + ".json")
    limits = _load(lpath) if os.path.exists(lpath) else {}
    e2e = tuple(m for m in bench["end_to_end"]
                if "workloads" not in m or name in m["workloads"])
    reported = {m["name"] for m in e2e}
    per_layer = tuple(m for m in bench["per_layer"]
                      if _reports(m, name, reported))
    return Cell(name, w["chips"], config, traffic, limits, e2e, per_layer)


def metric_reader(name: str):
    """The ``read(ctx)`` function of ``bench/metrics/<name>.py``."""
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def reference(name: str):
    """The plain reference module ``bench/reference/<name>.py``."""
    return importlib.import_module(f"bench.reference.{name}")


# top-level keys of a configuration file that are ModelConfig fields
_MODEL_KEYS = ("num_layers", "d_model", "num_heads", "num_kv_heads",
               "head_dim", "d_ff", "vocab_size", "rope_theta", "norm_eps",
               "qkv_bias", "tie_embeddings", "precision")


def model_config(config: dict, backend: str, rehearsal: bool = False):
    """The program's ``ModelConfig`` for a configuration file.

    It starts from the repo's registry entry (``arch``) and sets every
    number the file states; a registry field the file states otherwise
    is overridden, so the file is the configuration as it is run.  With
    ``rehearsal`` the registry's ``smoke_config`` sizes are used (CPU
    tests only) and the file's sizes are ignored."""
    import jax.numpy as jnp
    from repro.configs import get_config, smoke_config
    from repro.configs.base import MoESpec
    from repro.kernels.plan import KernelConfig
    kc = KernelConfig(backend=backend)
    if rehearsal:
        base = smoke_config(config["rehearsal"]["arch"])
        return dataclasses.replace(base, precision=config["precision"],
                                   kernel_config=kc)
    base = get_config(config["arch"])
    fields = {k: config[k] for k in _MODEL_KEYS}
    fields["dtype"] = jnp.dtype(config["dtype"])
    fields["moe"] = MoESpec(**config["moe"])
    return dataclasses.replace(base, kernel_config=kc, **fields)


def sizes(cfg) -> dict:
    """The numbers of a ``ModelConfig`` under the configuration file's
    keys: what the reference and the FLOP counts read.  ``num_experts``
    is the count of experts the program holds; ``router_experts`` the
    count its router scores, which is larger where the configuration
    holds one share of a layer's experts (the program's
    ``moe.router_experts`` where it has that field set, else
    ``num_experts``)."""
    m = cfg.moe
    return {
        "num_layers": cfg.num_layers, "d_model": cfg.d_model,
        "num_heads": cfg.num_heads, "num_kv_heads": cfg.num_kv_heads,
        "head_dim": cfg.resolved_head_dim, "d_ff": cfg.d_ff,
        "vocab_size": cfg.vocab_size, "rope_theta": cfg.rope_theta,
        "norm_eps": cfg.norm_eps, "qkv_bias": cfg.qkv_bias,
        "tie_embeddings": cfg.tie_embeddings,
        "moe": {"num_experts": m.num_experts,
                "router_experts": (getattr(m, "router_experts", None)
                                   or m.num_experts),
                "top_k": m.top_k,
                "d_ff_expert": m.d_ff_expert,
                "num_shared_experts": m.num_shared_experts,
                "norm_topk_prob": m.norm_topk_prob,
                "first_dense_layers": m.first_dense_layers},
    }


def traffic_sizes(traffic: dict, rehearsal: bool) -> dict:
    if not rehearsal:
        return traffic
    return {**traffic, **traffic.get("rehearsal", {})}
