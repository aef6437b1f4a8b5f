"""Calibrate the analytic cost model against compiled XLA cost_analysis.

At scan-free calibration points (1 layer per type, seq == chunk so every
inner scan has trip count 1, single device) the compiled ``flops`` must
match the analytic forward FLOPs within tolerance. This is what licenses
using the analytic model for the roofline at full scale, where XLA
undercounts scan bodies (EXPERIMENTS.md §Roofline methodology)."""

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import ModelConfig
from repro.models import costs, transformer


def _fwd_only(cfg):
    def fn(params, tokens):
        hidden, _, _ = transformer.forward_hidden(params, tokens, cfg)
        head = params["embed"]
        return transformer.losses.chunked_softmax_xent(
            hidden, head, tokens, cfg.vocab_size, chunk=cfg.xent_chunk
        )
    return fn


def _compiled_flops(cfg, b, s):
    params = jax.tree.map(
        lambda sp: jax.ShapeDtypeStruct(sp.shape, jnp.float32),
        transformer.param_specs(cfg),
        is_leaf=lambda x: hasattr(x, "axes"),
    )
    toks = jax.ShapeDtypeStruct((b, s), jnp.int32)
    c = jax.jit(_fwd_only(cfg)).lower(params, toks).compile()
    return float(c.cost_analysis()["flops"])


CASES = [
    # (name, layer_types, extra cfg) — seq = 128 = chunk: all scans trip=1
    ("dense", ("dense",), {}),
    ("moe", ("moe",), dict(num_experts=16, num_shared_experts=2, moe_top_k=4,
                           moe_d_ff=256, capacity_factor=1.25)),
    ("mla", ("mla_moe",), dict(num_experts=16, num_shared_experts=2,
                               moe_top_k=4, moe_d_ff=256, kv_lora_rank=64,
                               q_lora_rank=96, qk_rope_dim=16, qk_nope_dim=32,
                               v_head_dim=32)),
    ("mamba2", ("mamba2",), dict(ssm_state=32, ssm_head_dim=32)),
    ("mlstm", ("mlstm",), {}),
]


@pytest.mark.parametrize("name,types,extra", CASES)
def test_analytic_matches_compiled(name, types, extra):
    cfg = ModelConfig(
        name=f"calib-{name}", family="dense", num_layers=len(types),
        layer_types=types, d_model=256, num_heads=4, num_kv_heads=2,
        d_ff=512, vocab_size=512, attn_chunk_q=0, xent_chunk=128,
        moe_seq_chunk=512, remat="none", dtype="float32", **extra,
    )
    b, s = 4, 128
    got = _compiled_flops(cfg, b, s)
    want = costs.forward_flops(cfg, b, s, "train")
    rel = abs(got - want) / want
    assert rel < 0.15, f"{name}: compiled={got:.3e} analytic={want:.3e} rel={rel:.2%}"


def test_scan_undercount_demonstrated():
    """The reason the analytic model exists: XLA counts scan bodies once."""
    def body(x, w):
        return x @ w, None

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    ws = jax.ShapeDtypeStruct((8, 128, 128), jnp.float32)
    scanned = (
        jax.jit(lambda x, ws: jax.lax.scan(body, x, ws)[0])
        .lower(x, ws).compile().cost_analysis()["flops"]
    )
    assert scanned < 8 * 2 * 128**3 / 2  # counts ~1 body, not 8


def test_roofline_terms_sane():
    from repro.configs import get_config
    from repro.configs.shapes import SHAPES

    cfg = get_config("granite-3-8b")
    c = costs.step_cost(cfg, SHAPES["train_4k"], 256, {"data": 16, "model": 16})
    terms = costs.roofline_terms(c, 256)
    assert terms["dominant"] in ("compute", "memory", "collective")
    assert 0 < terms["roofline_fraction"] <= 1.0
    # train_4k on a 8B dense model: compute term must be O(0.1-10s)
    assert 0.01 < terms["compute_s"] < 100
    # decode must be memory-dominant
    c2 = costs.step_cost(cfg, SHAPES["decode_32k"], 256, {"data": 16, "model": 16})
    t2 = costs.roofline_terms(c2, 256)
    assert t2["dominant"] == "memory"
