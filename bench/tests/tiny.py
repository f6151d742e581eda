"""Tiny deployments, and end-to-end runs of them on the CPU."""
import json

import deploy
import run

ROOT = run.ROOT


def tiny_config(name: str) -> dict:
    """``name``'s configuration, cut to a size the CPU serves quickly."""
    cfg = json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())
    if cfg["kind"] == "tree":
        cfg["fit"] = {"max_depth": 8, "max_leaves": 48, "min_samples_leaf": 1}
        cfg["s"] = 32
    else:
        cfg["fit"] = {**cfg["fit"], "n_estimators": 3, "max_depth": 5}
    cfg["check_sample"] = 64
    dep = deploy.build(cfg, check_digest=False)
    cfg["digest"] = deploy.kind_module(cfg["kind"]).digest(dep.model)
    return cfg


def tiny_cell(workload: str, traffic: dict, trace: bool = False) -> run.Cell:
    """The workload's metrics on a tiny configuration and light traffic."""
    full = run.load_cell(workload, trace)
    return run.Cell(name=workload, chips=1,
                    config=tiny_config(full.config["name"]),
                    traffic=traffic, metrics=full.metrics)


def run_tiny(cell: run.Cell, seed: int = 3, seconds: float = 1.5,
             trace: bool = False):
    import time

    import jax
    return run.run_cell(cell, seed, seconds, trace,
                        t_start=time.perf_counter(),
                        devices=jax.devices()[:1])
