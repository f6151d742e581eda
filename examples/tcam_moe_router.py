"""Beyond-paper integration (DESIGN.md §4): an MoE layer whose routing
decisions come from a decision tree compiled to a TCAM LUT by the paper's
DT-HW compiler and evaluated in-graph as a ternary match.

    PYTHONPATH=src python examples/tcam_moe_router.py

Pipeline: distil a trained softmax router into a CART tree (teacher top-1
labels on hidden states) -> compile_router (parse / reduce / encode) ->
route via the bitplane match inside ``moe_ffn(router="tcam_dt")``.
"""
import dataclasses

import numpy as np

import jax
import jax.numpy as jnp

from repro import enable_compile_cache
from repro.core import predict, train_tree
from repro.models.config import ModelConfig
from repro.models.moe import moe_ffn
from repro.models.params import init_params
from repro.models.tcam_router import compile_router, route_tcam


def main():
    enable_compile_cache()
    cfg = ModelConfig(
        name="moe_demo", family="moe", n_layers=1, d_model=64, n_heads=4,
        n_kv_heads=4, head_dim=16, d_ff=256, vocab_size=1024,
        pattern=("attn+moe",), n_experts=8, experts_per_token=2,
        moe_d_ff=256, capacity_factor=4.0)
    p = jax.tree.map(
        lambda a: a[0],
        init_params(cfg, jax.random.PRNGKey(0))["blocks"]["attn+moe"])

    rng = np.random.default_rng(0)
    # "hidden states" + teacher softmax router top-1 labels
    H = rng.standard_normal((4096, cfg.d_model)).astype(np.float32)
    logits = H @ np.asarray(p["w_router"], np.float32)
    teacher = logits.argmax(-1).astype(np.int64)

    tree = train_tree(H, teacher, max_depth=10, max_leaves=256)
    agree_tree = float((predict(tree, H) == teacher).mean())
    bits = compile_router(tree)
    n_rows, n_bits = bits["is0"].shape
    print(f"distilled router tree: {tree.n_leaves} leaves "
          f"-> TCAM LUT {n_rows} x {n_bits}")
    print(f"tree vs teacher top-1 agreement: {agree_tree:.3f}")

    got = np.asarray(route_tcam(jnp.asarray(H), bits))
    assert (got == predict(tree, H)).all(), "TCAM match == tree (bijective)"
    print("in-graph TCAM routing == tree inference: OK")

    cfg_tcam = dataclasses.replace(cfg, router="tcam_dt")
    x = jnp.asarray(rng.standard_normal((2, 32, cfg.d_model)), jnp.float32)
    y_soft = moe_ffn(x, p, cfg)
    y_tcam = moe_ffn(x, p, cfg_tcam, router_bits=bits)
    print(f"moe_ffn(softmax) vs moe_ffn(tcam_dt): "
          f"output shapes {y_soft.shape} == {y_tcam.shape}, "
          f"mean |Δ| = {float(jnp.abs(y_soft - y_tcam).mean()):.4f} "
          f"(top-1 distilled vs top-2 soft: differences expected)")


if __name__ == "__main__":
    main()
