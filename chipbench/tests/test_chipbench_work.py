"""The work counts the per-layer metrics divide by: forward FLOPs per token
of each layer kind against XLA's own count for one layer of the program at
its published widths, and the least bytes of a commit."""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness, lm  # noqa: E402
from chipbench.references import dense, xlstm  # noqa: E402


def config(name):
    return harness.load_json(harness.BENCH_DIR / "configs" / f"{name}.json")


def layer_shapes(model, slot, key=None):
    p = model.lm.param_specs()["layers"][slot]
    p = p[key] if key else p
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
                        p)


def xla_flops(f, *args):
    return jax.jit(f).lower(*args).cost_analysis()["flops"]


def test_attn_mlp_layer_flops():
    """One attention + SwiGLU layer of granite-3-2b-d8 over 2048 tokens;
    the dense score matrix is computed whole, so all S keys count."""
    from repro.models.transformer import Slot
    cfg = config("granite-3-2b-d8")
    model, S = lm.Model(cfg), 2048
    x = jax.ShapeDtypeStruct((1, S, cfg["model"]["d_model"]), jnp.bfloat16)
    got = xla_flops(lambda p, x: model.lm._apply_slot(
        Slot("attn", "mlp"), p, x, mode="train", positions=jnp.arange(S))[0],
        layer_shapes(model, "slot0"), x)
    want = dense.fwd_flops_per_token(cfg["model"], S, causal=False)[
        "attn_mlp"] * S
    assert want <= got <= 1.02 * want


def test_mlstm_layer_flops():
    """One chunk (64 tokens), so the chunk scan runs once and XLA's count
    (which counts a loop body once) is the whole layer's."""
    from repro.models import xlstm as xm
    cfg = config("xlstm-125m")
    model, L = lm.Model(cfg), cfg["model"]["xlstm"]["chunk"]
    x = jax.ShapeDtypeStruct((1, L, cfg["model"]["d_model"]), jnp.bfloat16)
    got = xla_flops(lambda p, x: xm.mlstm_apply(
        p, x, n_heads=cfg["model"]["n_heads"], cfg=model.cfg.xlstm,
        mode="train")[0], layer_shapes(model, "slot0", "mlstm"), x)
    want = xlstm.fwd_flops_per_token(cfg["model"], L)["mlstm"] * L
    assert want <= got <= 1.02 * want


def test_slstm_layer_flops():
    """One token, so the time scan runs once.  XLA also counts the gates'
    elementwise work, which the matmul count leaves out (under 10% here)."""
    from repro.models import xlstm as xm
    cfg = config("xlstm-125m")
    model = lm.Model(cfg)
    x = jax.ShapeDtypeStruct((1, 1, cfg["model"]["d_model"]), jnp.bfloat16)
    got = xla_flops(lambda p, x: xm.slstm_apply(
        p, x, n_heads=cfg["model"]["n_heads"], mode="train")[0],
        layer_shapes(model, "slot1", "slstm"), x)
    want = xlstm.fwd_flops_per_token(cfg["model"], 1)["slstm"]
    assert want <= got <= 1.10 * want


@pytest.mark.parametrize("name,per_token", [
    ("xlstm-125m", 265_211_904), ("granite-3-2b-d8", 1_242_595_328)])
def test_forward_flops_per_token(name, per_token):
    cfg = config(name)
    ref = lm.Model(cfg).ref
    assert ref.fwd_flops_per_token(cfg["model"], 2048)["per_token"] == \
        per_token


def test_commit_least_bytes():
    """K slot deltas read, the params read and written, all bf16: 10 x
    162,402,096 x 2 bytes for xlstm-125m at K=8."""
    cell = harness.Cell("xlstm-125m.commit-secure8-k8")
    drv = cell.driver()
    model = lm.Model(cell.config)
    assert model.n_params == 162_402_096
    assert drv.commit_bytes(model, cell.traffic) == 10 * 162_402_096 * 2


def test_gaps_of_non_finite_readings_fail():
    """A program that returns NaN fails every number it touches instead of
    dropping out of a max or a median."""
    import numpy as np
    from chipbench import fl_reference as flr
    gaps, left_out = flr.leaf_gaps([1.0, np.nan, 2.0], [1.0, 1.0, 2.0],
                                   [1.0, 1.0, 2.0])
    assert gaps[1] == np.inf and np.nanmax(gaps) == np.inf
    assert left_out == []
    assert flr.rel_gap(float("nan"), 1.0) == float("inf")
    assert not flr.rel_gap(float("nan"), 1.0) <= 1.0
