"""Everything PR 29 added for Laguna-S-2.1, each against a reference, in
ONE file — so that, under ``--dist loadfile``, these compile-heavy tests
hold one worker at a time and never three.

1. The flash kernels' grouped queries and window, in interpret mode
   against a plain oracle: {MHA, 6:1, 9:1 groups} × {causal, a window
   shorter than a tile, a window spanning tiles, a window ≥ T} —
   forward, dq, dk, dv — in both addresses a head's tiles can have (the
   projections' own layout at dh 128, head-major at dh 16), fused and
   separate; the band's tile counts against a brute-force count.
2. The per-unit options — grouped queries, a head size of its own, the
   window, the per-head gate, partial rotary with YaRN; sigmoid scores,
   the routed scaling, the shared expert, the held share; the dense
   gated MLP — XLA path against the numpy oracle: output, err_input and
   every parameter after two momentum steps.  With every option unset
   the units build what they built (the other test files hold that).
3. The toy Laguna ``StandardWorkflow`` (``znbench/tests/data/toy``: the
   dense block under full attention, one window block and the full
   block of its table, the expert layers holding 4 of 16 experts;
   hidden 64, heads of 16, 4 / 6 query heads on 2 K/V heads, window 8,
   T 32) against the benchmark's plain reference
   (``znbench/reference/laguna.py``) on seeded weights, in f32 with the
   kernels interpreted: every layer's output, the loss, EVERY gradient
   against the reference's ``jax.value_and_grad``; the YaRN tables
   against a float64 transcription; the share test (the shares' routed
   parts add up to the uncut layer); each left-out term fails the
   cell's stated tolerance; a step over the held share's buffer is
   poisoned, not cut short."""

import copy
import importlib.util
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from znicz_tpu.backends import NumpyDevice, XLADevice
from znicz_tpu.dummy import DummyUnit, DummyWorkflow
from znicz_tpu.loader.base import TRAIN
from znicz_tpu.loader.fullbatch import ArrayLoader
from znicz_tpu.memory import Vector
from znicz_tpu.models.standard_workflow import (StandardWorkflow,
                                                 layer_type)
from znicz_tpu.observe import metrics as obs_metrics
from znicz_tpu.ops import attention, moe
from znicz_tpu.ops import pallas_attention as pa
from znicz_tpu.utils import prng
from znicz_tpu.utils.config import root
from znicz_tpu.workflow import Workflow


# ======================================================================
# 1. the kernels: grouped queries and the window
# ======================================================================
def _rand(shape, seed):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(shape),
                       jnp.float32)


def oracle(q, k, v, window):
    """(B, T, H, dh) × (B, T, H_kv, dh): plain softmax attention, query
    head h on K/V head h // group, causal, columns > row − window."""
    b, t, h, dh = q.shape
    group = h // k.shape[2]
    k, v = (jnp.repeat(a, group, axis=2) for a in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(dh)
    rows, cols = np.arange(t)[:, None], np.arange(t)[None, :]
    keep = rows >= cols
    if window is not None:
        keep &= cols > rows - window
    s = jnp.where(jnp.asarray(keep), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)


BAND_T, TILE = 64, 16
WINDOWS = {"causal": None, "short": 5, "spanning": 24, "whole": 64}
GROUPS = {"mha": (4, 4), "6to1": (12, 2), "9to1": (9, 1)}


#: every group × window head-major (dh 16); in the projections' own
#: layout (dh 128) the causal call and the window that spans tiles
CASES = [(group, window, dh) for dh in (16, 128) for group in GROUPS
         for window in WINDOWS
         if dh == 16 or window in ("causal", "spanning")]


@pytest.mark.parametrize("group,window,dh", CASES)
def test_group_and_window_match_the_oracle(group, window, dh):
    h, h_kv = GROUPS[group]
    if dh == 128:             # the boundary layout, at fewer heads
        h, h_kv = (2, 2) if group == "mha" else (h // h_kv, 1)
    w = WINDOWS[window]
    q = _rand((1, BAND_T, h, dh), 1)
    k = _rand((1, BAND_T, h_kv, dh), 2)
    v = _rand((1, BAND_T, h_kv, dh), 3)
    weight = _rand((1, BAND_T, h, dh), 4)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * weight)

    def kernel(q, k, v):
        return pa.flash_attention(q, k, v, causal=True, block_q=TILE,
                                  block_k=TILE, sub_tile=(8, 8),
                                  interpret=True, window=w)

    want = oracle(q, k, v, w)
    got = kernel(q, k, v)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    g_want = jax.grad(loss(lambda *a: oracle(*a, w)), (0, 1, 2))(q, k, v)
    g_got = jax.grad(loss(kernel), (0, 1, 2))(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), g_got, g_want):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4,
                                   err_msg=name)


@pytest.mark.parametrize("window", [None, 24])
@pytest.mark.parametrize("dh", [16, 128])
def test_fused_projection_with_grouped_queries(dh, window):
    """ONE (B, T, (H + 2·H_kv)·dh) array in, ONE cotangent out."""
    h, h_kv = 3, 1
    qkv = _rand((2, BAND_T, (h + 2 * h_kv) * dh), 5)
    weight = _rand((2, BAND_T, h * dh), 6)

    def split(a):
        q, k, v = jnp.split(a, [h * dh, (h + h_kv) * dh], axis=-1)
        return (q.reshape(2, BAND_T, h, dh), k.reshape(2, BAND_T, h_kv, dh),
                v.reshape(2, BAND_T, h_kv, dh))

    def kernel(a):
        return pa.flash_attention_rows(
            (a,), h, causal=True, block_q=TILE, block_k=TILE,
            sub_tile=(8, 8), interpret=True, n_kv_heads=h_kv,
            window=window)

    def plain(a):
        return oracle(*split(a), window).reshape(2, BAND_T, h * dh)

    np.testing.assert_allclose(kernel(qkv), plain(qkv), atol=2e-5,
                               rtol=2e-5)
    g_got = jax.grad(lambda a: jnp.sum(kernel(a) * weight))(qkv)
    g_want = jax.grad(lambda a: jnp.sum(plain(a) * weight))(qkv)
    np.testing.assert_allclose(g_got, g_want, atol=1e-4, rtol=1e-4)


def test_uneven_tiles_and_a_band_that_ends_past_the_sequence():
    """bq ≠ bk, and K tiles whose band's last Q tiles lie past T."""
    q, k, v = (_rand((1, 96, 2, 16), s) for s in (7, 8, 9))
    for bq, bk, w in ((32, 16, 20), (16, 32, 40), (48, 96, 7)):
        got = pa.flash_attention(q, k, v, causal=True, block_q=bq,
                                 block_k=bk, interpret=True, window=w)
        np.testing.assert_allclose(got, oracle(q, k, v, w), atol=2e-5,
                                   rtol=2e-5, err_msg=str((bq, bk, w)))
        g_got = jax.grad(lambda *a: jnp.sum(pa.flash_attention(
            *a, causal=True, block_q=bq, block_k=bk, interpret=True,
            window=w) ** 2), (0, 1, 2))(q, k, v)
        g_want = jax.grad(lambda *a: jnp.sum(oracle(*a, w) ** 2),
                          (0, 1, 2))(q, k, v)
        for a, b in zip(g_got, g_want):
            np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


def test_a_window_is_named_and_refuses_what_it_cannot_do():
    q = _rand((1, BAND_T, 2, 16), 1)
    def loss(q, window, block_k=16):
        return jnp.sum(pa.flash_attention(
            q, q, q, causal=True, block_q=16, block_k=block_k,
            interpret=True, window=window))

    banded = str(jax.make_jaxpr(jax.grad(lambda q: loss(q, 8)))(q))
    plain = str(jax.make_jaxpr(jax.grad(lambda q: loss(q, None)))(q))
    # each backward is ONE kernel (PR 55: past one K tile the dq tiles
    # wait in VMEM), the band's named for its window
    for name in ("znicz_flash_fwd", "znicz_flash_bwd"):
        assert name + "_win" in banded and name + "_win" not in plain
        assert name in plain
    for text in (banded, plain):
        assert "znicz_flash_dq" not in text
        assert "znicz_flash_dkv" not in text
    # under ONE K tile too
    banded = str(jax.make_jaxpr(jax.grad(
        lambda q: loss(q, 8, BAND_T)))(q))
    plain = str(jax.make_jaxpr(jax.grad(
        lambda q: loss(q, None, BAND_T)))(q))
    assert "znicz_flash_bwd" in plain and "znicz_flash_dq" not in plain
    assert "znicz_flash_bwd_win" in banded
    assert "znicz_flash_dq_win" not in banded
    with pytest.raises(ValueError, match="window"):
        pa.flash_attention(q, q, q, causal=False, interpret=True,
                           window=8)
    with pytest.raises(ValueError, match="window"):
        pa.flash_attention(q, q, q, causal=True, interpret=True,
                           window=8, q_offset=16)
    with pytest.raises(ValueError, match="divide"):
        pa.flash_attention(_rand((1, BAND_T, 3, 16), 1), q, q, causal=True,
                           interpret=True)


def _brute(t, sq, sk, window):
    rows, cols = np.arange(t)[:, None], np.arange(t)[None, :]
    keep = (rows >= cols) & (cols > rows - window)
    causal = rows >= cols
    counts = {"interior": 0, "crossing": 0, "skipped": 0, "band_edge": 0}
    for r in range(0, t, sq):
        for c in range(0, t, sk):
            tile = keep[r:r + sq, c:c + sk]
            if not tile.any():
                counts["skipped"] += 1
            elif tile.all():
                counts["interior"] += 1
            elif not causal[r:r + sq, c:c + sk].all():
                counts["crossing"] += 1
            else:
                counts["band_edge"] += 1
    return counts


@pytest.mark.parametrize("t,sq,sk,window", [
    (64, 8, 8, 5), (64, 16, 16, 16), (64, 16, 8, 24), (96, 32, 16, 20),
    (128, 16, 32, 40), (64, 8, 8, 64), (64, 8, 8, 1)])
def test_band_tile_counts_match_a_brute_force_count(t, sq, sk, window):
    got = pa.causal_tile_counts(t, t, sq, sk, sq, sk, window=window)
    want = _brute(t, sq, sk, window)
    assert {k: got[k] for k in want} == want
    total = (t // sq) * (t // sk)
    assert got["executed_share"] == pytest.approx(
        1 - want["skipped"] / total)
    # what the tiling runs is never less than the band
    assert got["executed_share"] >= pa.band_share(t, window) - 1e-12


def test_band_share_and_steps_at_the_cell_s_shapes():
    assert pa.band_share(8192, None) == pytest.approx(
        8192 * 8193 / 2 / 8192 ** 2)
    assert pa.band_share(8192, 512) == pytest.approx(
        (512 * 513 / 2 + 7680 * 512) / 8192 ** 2)
    # a 512-tile row block's band of 512 touches two K tiles
    assert pa.band_steps(8192, 512, 512, 512) == (2, 2)
    assert pa.band_steps(8192, 256, 256, 512) == (3, 3)
    counts = pa.causal_tile_counts(8192, 8192, 512, 512, 512, 512,
                                   window=512)
    assert counts["skipped"] == 256 - 31
    assert counts["executed_share"] == pytest.approx(31 / 256)


# ======================================================================
# 2. the units' options against the numpy oracle
# ======================================================================
B, T, D = 2, 16, 24

YARN = {"factor": 8, "original_max_position_embeddings": 8,
        "beta_fast": 32, "beta_slow": 1,
        "attention_factor": 1.2079441541679836}

ATTENTION = {
    "grouped": dict(n_heads=6, n_kv_heads=2, head_dim=8),
    "window": dict(n_heads=3, window=5),
    "gate": dict(n_heads=3, head_gate=True),
    "yarn": dict(n_heads=3, rope={"theta": 500000.0, "rotary_dim": 4,
                                  "yarn": YARN}),
    "laguna_sliding": dict(n_heads=6, n_kv_heads=2, head_dim=8,
                           window=5, head_gate=True, pre_norm="rms",
                           residual=True, rope={"theta": 10000.0}),
    "laguna_full": dict(n_heads=4, n_kv_heads=2, head_dim=8,
                        head_gate=True, pre_norm="rms", residual=True,
                        qk_norm="rms",
                        rope={"theta": 500000.0, "rotary_dim": 4,
                              "yarn": YARN}),
}

MOE = {
    "sigmoid": dict(score="sigmoid", norm_topk=True),
    "shared": dict(shared_width=10),
    "held": dict(held=[1, 4, 6]),
    "laguna": dict(score="sigmoid", norm_topk=True, routed_scale=2.5,
                   shared_width=10, held=[0, 2, 5, 7]),
}


def _build(device, x, make, pair, params=None):
    prng.seed_all(5)
    wf = DummyWorkflow()
    src = DummyUnit(wf, output=Vector(np.asarray(x), name="x"))
    fwd = make(wf)
    fwd.link_attrs(src, ("input", "output"))
    fwd.initialize(device=device)
    rng = np.random.default_rng(3)
    for attr in ("gain_norm", "gain_q", "gain_k"):   # not all ones
        vec = getattr(fwd, attr, None)
        if vec:
            vec.reset(rng.uniform(0.5, 1.5, vec.shape).astype(np.float32))
            vec.initialize(device)
    for attr, arr in (params or {}).items():
        vec = getattr(fwd, attr)
        vec.reset(np.array(arr, np.float32))
        vec.initialize(device)
    gd_u = pair(wf, learning_rate=0.05, gradient_moment=0.9)
    gd_u.forward_unit = fwd
    gd_u.link_attrs(fwd, "input", "output", "weights", "bias")
    gd_u.err_output = Vector(np.zeros(np.shape(x), np.float32),
                             name="err")
    gd_u.initialize(device=device)
    return fwd, gd_u


def _params(fwd) -> dict:
    out = {}
    for attr in fwd.EXPORT_PARAMS:
        vec = getattr(fwd, attr)
        if vec:
            vec.map_read()
            out[attr] = np.array(vec.mem, np.float32)
    return out


def _two_steps(fwd, gd_u, err) -> dict:
    for _ in range(2):
        fwd.run()
        gd_u.err_output.reset(err.copy())
        gd_u.err_output.initialize(fwd.device)
        gd_u.run()
    fwd.output.map_read()
    gd_u.err_input.map_read()
    return {**_params(fwd),
            "output": np.array(fwd.output.mem, np.float32),
            "err_input": np.array(gd_u.err_input.mem, np.float32)}


def _agree(make, pair, expect_params, kernels=False):
    if kernels:
        root.common.engine.pallas_interpret = True
        root.common.engine.flash_attention = True
        root.common.engine.moe_grouped_matmul = True
    rng = np.random.default_rng(0)
    x = rng.normal(0, 1.0, (B, T, D)).astype(np.float32)
    err = rng.normal(0, 0.1, (B, T, D)).astype(np.float32)
    np_f, np_g = _build(NumpyDevice(), x, make, pair)
    drawn = _params(np_f)
    assert set(drawn) == set(expect_params)
    xla_f, xla_g = _build(XLADevice(), x, make, pair, params=drawn)
    want, got = _two_steps(np_f, np_g, err), _two_steps(xla_f, xla_g, err)
    for key, value in want.items():
        np.testing.assert_allclose(got[key], value, rtol=2e-3, atol=3e-5,
                                   err_msg=key)
    for attr in drawn:            # and every parameter MOVED
        assert np.abs(want[attr] - drawn[attr]).max() > 0, attr
    return xla_f


@pytest.mark.parametrize("kernels", [False, True],
                         ids=["plain_core", "flash_interpreted"])
@pytest.mark.parametrize("case", list(ATTENTION))
def test_attention_options_against_the_numpy_oracle(case, kernels):
    options = dict(ATTENTION[case], causal=True, include_bias=False)
    expect = {"weights", "weights_out"}
    expect |= {"weights_head_gate"} if options.get("head_gate") else set()
    expect |= {"gain_norm"} if options.get("pre_norm") else set()
    expect |= {"gain_q", "gain_k"} if options.get("qk_norm") else set()
    unit = _agree(lambda wf: attention.MultiHeadAttention(wf, **options),
                  attention.GDMultiHeadAttention, expect, kernels)
    assert unit._flash.runs == kernels
    heads = options["n_heads"]
    dh = options.get("head_dim") or D // heads
    kv = options.get("n_kv_heads") or heads
    assert unit.weights.shape == (D, (heads + 2 * kv) * dh)
    assert unit.weights_out.shape == (heads * dh, D)


@pytest.mark.parametrize("kernels", [False, True],
                         ids=["ragged_dot", "kernels_interpreted"])
@pytest.mark.parametrize("case", list(MOE))
def test_expert_layer_options_against_the_numpy_oracle(case, kernels):
    options = dict(n_experts=8, top_k=3, width=12, pre_norm="rms",
                   residual=True, aux_loss_weight=0.01,
                   z_loss_weight=0.001, **MOE[case])
    expect = {"weights", "weights_gate", "weights_up", "weights_down",
              "gain_norm"}
    if options.get("shared_width"):
        expect |= set(moe.MoE.SHARED)
    unit = _agree(lambda wf: moe.MoE(wf, **options), moe.GDMoE, expect,
                  kernels)
    held = options.get("held")
    assert unit.weights_gate.shape[0] == (len(held) if held else 8)
    assert unit.weights.shape == (D, 8)        # the router: all experts


def test_the_dense_gated_mlp_against_the_numpy_oracle():
    assert layer_type("gated_mlp") is moe.GatedMLP
    unit = _agree(
        lambda wf: moe.GatedMLP(wf, width=40, pre_norm="rms",
                                residual=True, norm_eps=1e-6),
        moe.GDGatedMLP, {"weights", "weights_up", "weights_down",
                         "gain_norm"})
    assert unit.weights.shape == (D, 40)
    bare = _agree(lambda wf: moe.GatedMLP(wf, width=8),
                  moe.GDGatedMLP, {"weights", "weights_up",
                                   "weights_down"})
    assert not bare.gain_norm


def test_what_the_options_refuse():
    wf = DummyWorkflow()
    with pytest.raises(ValueError, match="divide"):
        attention.MultiHeadAttention(wf, n_heads=6, n_kv_heads=4)
    with pytest.raises(ValueError, match="causal"):
        attention.MultiHeadAttention(wf, n_heads=2, window=4)
    with pytest.raises(ValueError, match="score"):
        moe.MoE(wf, n_experts=4, top_k=1, width=8, score="tanh")
    with pytest.raises(ValueError, match="held"):
        moe.MoE(wf, n_experts=4, top_k=1, width=8, held=[4])
    with pytest.raises(ValueError, match="held"):
        moe.MoE(wf, n_experts=4, top_k=1, width=8, held=[])
    # the ring and the scan-blocked core do not know the band
    x = np.zeros((B, T, D), np.float32)
    for bad in (dict(flash_block_k=8), dict(seq_parallel=True)):
        src = DummyUnit(wf, output=Vector(x, name="x"))
        unit = attention.MultiHeadAttention(wf, n_heads=3, causal=True,
                                            window=4, **bad)
        unit.link_attrs(src, ("input", "output"))
        with pytest.raises(ValueError, match="window"):
            unit.initialize(device=XLADevice())


def test_a_shape_the_kernels_cannot_tile_keeps_its_band():
    """T 12 tiles by no block the kernels have: the unit takes the
    plain core, and the output is the banded one, not the causal."""
    root.common.engine.pallas_interpret = True
    root.common.engine.flash_attention = True
    rng = np.random.default_rng(1)
    x = rng.normal(0, 1.0, (1, 12, D)).astype(np.float32)
    outs = {}
    for window in (3, None):
        fwd, _ = _build(XLADevice(), x, lambda wf: attention.
                        MultiHeadAttention(wf, n_heads=3, causal=True,
                                           window=window),
                        attention.GDMultiHeadAttention)
        assert not fwd._flash.runs
        fwd.run()
        fwd.output.map_read()
        outs[window] = np.array(fwd.output.mem)
        ref, _ = _build(NumpyDevice(), x, lambda wf: attention.
                        MultiHeadAttention(wf, n_heads=3, causal=True,
                                           window=window),
                        attention.GDMultiHeadAttention,
                        params=_params(fwd))
        ref.run()
        np.testing.assert_allclose(outs[window], ref.output.mem,
                                   atol=2e-5)
    assert np.abs(outs[3] - outs[None]).max() > 1e-2


@pytest.mark.parametrize("option", [
    {"n_kv_heads": 1}, {"head_dim": 16}, {"window": 4},
    {"head_gate": True}])
def test_serving_refuses_the_new_attention_options_by_name(option):
    from znicz_tpu.export import refuse_unserved
    unit = attention.MultiHeadAttention(DummyWorkflow(), n_heads=2,
                                        causal=True, **option)
    with pytest.raises(NotImplementedError, match=next(iter(option))):
        refuse_unserved([unit], "DecodeModel")


def test_serving_refuses_the_dense_gated_mlp_by_name():
    from znicz_tpu.export import refuse_unserved
    with pytest.raises(NotImplementedError, match="gated_mlp"):
        refuse_unserved([moe.GatedMLP(DummyWorkflow(), width=8)],
                        "export_forward")


# ======================================================================
# 3. the toy model against the benchmark's plain reference
# ======================================================================
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ, BATCH = 32, 2


def reference():
    path = os.path.join(REPO, "znbench", "reference", "laguna.py")
    spec = importlib.util.spec_from_file_location("ref_laguna", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def toy_config() -> dict:
    with open(os.path.join(REPO, "znbench", "tests", "data", "toy",
                           "configs", "laguna_s_2_1.json")) as fh:
        return json.load(fh)


#: of the toy cell's thirteen layers: the embedding, the dense block
#: under full attention, ONE sliding and the full expert block, the head
#: (the cell's two further sliding blocks repeat the one kept)
KEPT = (0, 1, 2, 3, 4, 9, 10, 11, 12)


def layers(lr: float, moment: float) -> list:
    table = copy.deepcopy(toy_config()["workflow"]["layers"])
    table = [table[i] for i in KEPT]
    for layer in table:
        layer["<-"] = {"learning_rate": lr, "gradient_moment": moment}
    return table


def params_of(wf) -> dict:
    out = {}
    for i, unit in enumerate(wf.forwards):
        for attr in unit.EXPORT_PARAMS:
            vec = getattr(unit, attr)
            if vec:
                vec.map_read()
                out[f"layer{i}_{attr}"] = np.array(vec.mem, np.float32)
    return out


@pytest.fixture(scope="module")
def one_step():
    """One plain-SGD step at lr 1 (W −= gradient) of the system, f32,
    flash and grouped-matmul kernels interpreted, with the parameters
    before it and the tokens it saw."""
    from znicz_tpu.utils.config import reset_root, root
    reset_root()
    engine = root.common.engine
    engine.pallas_interpret = True
    engine.flash_attention = True
    engine.moe_grouped_matmul = True
    vocab = toy_config()["input"]["vocab"]
    rng = np.random.default_rng(17)
    ids = rng.integers(0, vocab, (BATCH, SEQ + 1))
    x, y = ids[:, :-1], ids[:, 1:]
    prng.seed_all(31)
    table = layers(1.0, 0.0)
    wf = StandardWorkflow(
        name="laguna_ref",
        loader_factory=lambda w: ArrayLoader(
            w, train_data=x.astype(np.float32),
            train_labels=y.astype(np.int32), minibatch_size=BATCH,
            shuffle_limit=0),
        layers=table, decision_config={"max_epochs": 1})
    wf.initialize(device=XLADevice())
    rng = np.random.default_rng(18)
    for unit in wf.forwards:      # gains of one would hide their path
        vec = getattr(unit, "gain_norm", None)
        if vec:
            vec.map_invalidate()
            vec.mem[...] = rng.uniform(0.7, 1.3, vec.shape)
    before = params_of(wf)
    wf.run()
    reset_root()
    return wf, table, before, x, y


def test_the_toy_model_is_the_cell_s_model_in_small(one_step):
    wf, table, *_ = one_step
    assert [layer["type"] for layer in table] == [
        "embedding", "attention", "gated_mlp", "attention", "moe",
        "attention", "moe", "rms_norm", "softmax"]
    units = wf.forwards
    assert [u.n_heads for u in units if hasattr(u, "n_heads")] \
        == [4, 6, 4]
    assert [u.window for u in units if hasattr(u, "window")] \
        == [None, 8, None]
    for unit in units:
        if isinstance(unit, attention.MultiHeadAttention):
            assert unit._flash.runs and unit._flash.head_pack == 1
            assert unit.weights.shape == (64, (unit.n_heads + 4) * 16)
            assert unit.weights_head_gate.shape == (64, unit.n_heads)
        if isinstance(unit, moe.MoE):
            assert unit.weights_gate.shape == (4, 64, 32)   # held only
            assert unit.weights.shape == (64, 16)           # all outputs
            assert unit.last_choice.shape == (BATCH, SEQ, 3)


def test_layer_outputs_and_probabilities(one_step):
    """f32 on both sides: what is left is the order of summation, 1e-5
    of a layer's range; 1e-4 is a hundred times under what bf16
    anywhere would leave."""
    wf, table, before, x, y = one_step
    ref = reference()
    outs, router = ref.run(before, table, x)
    assert len(outs) == len(wf.forwards) == len(KEPT)
    for i, (unit, want) in enumerate(zip(wf.forwards, outs)):
        unit.output.map_read()
        got = np.asarray(unit.output.mem, np.float32).reshape(want.shape)
        err = np.abs(got - np.asarray(want)).max() \
            / (np.abs(np.asarray(want)).max() + 1e-12)
        assert err < 1e-4, (i, table[i]["type"], err)
    for i, unit in enumerate(wf.forwards):
        if table[i]["type"] != "moe":
            continue
        unit.router_logits.map_read()
        unit.last_choice.map_read()
        np.testing.assert_allclose(
            unit.router_logits.mem.reshape(-1, 16),
            np.asarray(router["logits"][i]), rtol=1e-4, atol=1e-5)
        np.testing.assert_array_equal(
            np.sort(unit.last_choice.mem.reshape(-1, 3), axis=-1),
            np.sort(router["chosen"][i], axis=-1))


def test_loss_and_every_gradient(one_step):
    """The step ran plain SGD at lr 1, so parameter − parameter after
    IS the system's gradient of CE + 0.01·lb: compared with the
    reference's ``value_and_grad`` for every tensor, 1e-3 of each
    gradient's largest entry."""
    wf, table, before, x, y = one_step
    ref = reference()
    value, grads = ref.loss_and_grads(before, table, x, y)
    after = params_of(wf)
    # embedding, 3 × (qkv, out, gate, gain), dense (3 + gain), 2 ×
    # (router, 3 slabs, 3 shared, gain), final gain, head
    assert set(grads) == set(before)
    assert len(before) == 1 + 3 * 4 + 4 + 2 * 8 + 2
    for name, want in grads.items():
        got = before[name] - after[name]
        scale = np.abs(want).max()
        assert scale > 0, name
        assert np.abs(got - want).max() <= 1e-3 * scale, (
            name, np.abs(got - want).max() / scale)
    ce = wf.decision.epoch_loss[TRAIN]
    aux = sum(0.01 * obs_metrics.moe_aux_loss(
        unit.name, "load_balance").value
        for i, unit in enumerate(wf.forwards)
        if table[i]["type"] == "moe")
    assert ce + aux == pytest.approx(value, rel=1e-4)


def test_what_the_expert_layers_report(one_step):
    wf, table, *_ = one_step
    for i, unit in enumerate(wf.forwards):
        if table[i]["type"] != "moe":
            continue
        held = {stat: obs_metrics.moe_held(unit.name, stat).value
                for stat in ("held", "of", "rows_here", "rows_routed",
                             "capacity", "rows_over")}
        assert held["held"] == 4 and held["of"] == 16
        assert held["rows_routed"] == BATCH * SEQ * 3
        assert 0 < held["rows_here"] < held["rows_routed"]
        assert held["rows_over"] == 0
        unit.last_choice.map_read()
        chosen = unit.last_choice.mem.reshape(-1)
        assert held["rows_here"] == np.isin(chosen, unit.held).sum()
        mean = obs_metrics.moe_expert_tokens(unit.name, "mean").value
        assert mean == pytest.approx(held["rows_here"] / 4)
    text = obs_metrics.REGISTRY.to_prometheus()
    assert "znicz_moe_held{" in text and "znicz_flash_band{" in text
    groups = {unit._flash.n_heads // unit._flash.n_kv_heads
              for unit in wf.forwards if hasattr(unit, "n_kv_heads")}
    assert {2, 3} <= groups
    windowed = wf.forwards[3]
    assert obs_metrics.flash_band(windowed.name, "window").value == 8
    assert obs_metrics.flash_band(windowed.name, "band_share").value \
        == pytest.approx((8 * 9 / 2 + 24 * 8) / 32 ** 2)
    plan = windowed._flash
    assert plan.window == 8 and plan.tiles["band_edge"] >= 0
    assert obs_metrics.flash_band(
        windowed.name, "executed_share").value \
        == plan.tiles["executed_share"]
    assert "%d query heads to a K/V head, window 8" % (
        plan.n_heads // plan.n_kv_heads) in plan.line()
    # the band's backward is one pass whose dq tiles wait in a ring of
    # slots (PR 55): the gauge and the line say so
    assert plan.backward == 1 and plan.resident_dq > 0
    assert obs_metrics.flash_backward(
        windowed.name, "resident_dq_bytes").value == plan.resident_dq
    assert "backward passes 1 (" in plan.line()


# ----------------------------------------------------------------------
# each left-out term fails the cell's tolerance
# ----------------------------------------------------------------------
def _without(table, before, what):
    """The reference's model with one term left out."""
    table, params = copy.deepcopy(table), dict(before)
    for i, layer in enumerate(table):
        spec = layer["->"]
        if what == "gate" and spec.get("head_gate"):
            spec["head_gate"] = False
        if what == "shared expert" and spec.get("shared_width"):
            spec["shared_width"] = 0
        if what == "band" and spec.get("window"):
            spec["window"] = None
        if what == "scaling 2.5" and spec.get("routed_scale"):
            spec["routed_scale"] = 1.0
        if what == "head count" and spec.get("n_heads") == 6:
            # 4 heads for 6: the last two heads' outputs are not added
            w_out = np.array(params[f"layer{i}_weights_out"])
            w_out[4 * 16:] = 0.0
            params[f"layer{i}_weights_out"] = w_out
        if what == "yarn" and spec.get("rope", {}).get("yarn"):
            spec["rope"] = {"theta": spec["rope"]["theta"],
                            "rotary_dim": spec["rope"]["rotary_dim"]}
        if what == "partial rotary" and spec.get("rope", {}).get(
                "rotary_dim"):
            spec["rope"] = dict(spec["rope"], rotary_dim=16)
    return table, params


@pytest.mark.parametrize("what", ["gate", "shared expert", "band",
                                  "head count", "scaling 2.5", "yarn",
                                  "partial rotary"])
def test_a_left_out_term_fails_the_stated_tolerance(one_step, what):
    wf, table, before, x, y = one_step
    limit = toy_config()["reference_tolerance"]["layers"]
    ref = reference()
    routing = ref.run(before, table, x)[1]["chosen"]
    wrong_table, wrong_params = _without(table, before, what)
    outs = ref.forward(wrong_params, wrong_table, x, routing)
    worst = 0.0
    for unit, want in zip(wf.forwards[1:], outs[1:]):
        unit.output.map_read()
        got = np.asarray(unit.output.mem, np.float32).reshape(want.shape)
        worst = max(worst, np.abs(got - want).max()
                    / (np.abs(want).max() + 1e-12))
    assert worst > limit, (what, worst)


# ----------------------------------------------------------------------
# the share test (model-configs guide, section 4)
# ----------------------------------------------------------------------
def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Over a partition of the 16 experts into shares of 4, 4, 5 and 3,
    what each chip's layer adds for its own experts, with what every
    chip computes alike (the residual, the shared expert) counted once,
    adds up to the uncut reference's output of the layer."""
    ref = reference()
    rng = np.random.default_rng(5)
    d, width, experts, top_k = 64, 32, 16, 3
    spec = {"n_experts": experts, "top_k": top_k, "width": width,
            "norm_topk": True, "score": "sigmoid", "routed_scale": 2.5,
            "shared_width": 32, "pre_norm": "rms", "residual": True,
            "aux_loss_weight": 0.01, "norm_eps": 1e-6}
    full = {"layer0_weights": rng.normal(0, 0.5, (d, experts)),
            "layer0_gain_norm": rng.uniform(0.7, 1.3, d)}
    for name, shape in (("gate", (experts, d, width)),
                        ("up", (experts, d, width)),
                        ("down", (experts, width, d))):
        full[f"layer0_weights_{name}"] = rng.normal(0, 0.2, shape)
        full[f"layer0_weights_shared_{name}"] = rng.normal(
            0, 0.2, shape[1:])
    full = {k: v.astype(np.float32) for k, v in full.items()}
    x = rng.normal(0, 1, (BATCH, SEQ, d)).astype(np.float32)
    import jax
    with jax.default_matmul_precision("highest"):
        uncut, _, _, chosen = ref.moe_block(x, full, 0, spec)
        # what every chip computes alike: the reference with no expert
        alike = ref.moe_block(x, full, 0, spec, chosen, held=[])[0]
    assert np.abs(np.asarray(uncut) - np.asarray(alike)).max() > 0.1

    shares = [[0, 1, 2, 3], [4, 6, 8, 10], [5, 7, 9, 11, 12], [13, 14, 15]]
    assert sorted(e for share in shares for e in share) \
        == list(range(experts))
    total = np.asarray(alike, np.float64)
    for share in shares:
        wf = Workflow(name="share")
        unit = moe.MoE(wf, held=share, **spec)
        unit.input = Vector(x.copy())
        for attr in unit.EXPORT_PARAMS:
            value = full[f"layer0_{attr}"]
            if attr in ("weights_gate", "weights_up", "weights_down"):
                value = value[share]        # this chip's slabs
            getattr(unit, attr).reset(value.copy())
        unit.initialize(device=XLADevice())
        unit.run()
        unit.output.map_read()
        unit.last_choice.map_read()
        np.testing.assert_array_equal(
            np.sort(unit.last_choice.mem.reshape(-1, top_k), axis=-1),
            np.sort(chosen, axis=-1))     # every chip routes over all 16
        mine = np.asarray(unit.output.mem, np.float64)
        # … and the reference given the same share agrees with the chip
        same = ref.moe_block(x, {**full, **{
            f"layer0_weights_{n}": full[f"layer0_weights_{n}"][share]
            for n in ("gate", "up", "down")}}, 0, spec, chosen,
            held=share)[0]
        np.testing.assert_allclose(mine, np.asarray(same), atol=2e-5)
        total += mine - np.asarray(alike, np.float64)
    np.testing.assert_allclose(total, np.asarray(uncut), atol=1e-4)


def test_a_step_over_the_buffer_is_poisoned_not_cut_short():
    """The held share's buffer is ``HELD_SLACK`` times the uniform
    share (here N rows of the 2 N that can arrive).  A step within it is
    the dropless layer (the numpy oracle); one whose router sends every
    pair here comes out NaN — the guard refuses it — and is counted."""
    rng = np.random.default_rng(6)
    x = rng.normal(0, 1, (BATCH, SEQ, 64)).astype(np.float32)
    x[..., 0] = 4.0
    pairs = BATCH * SEQ * 2

    def run(collapse):
        prng.seed_all(3)
        wf = Workflow(name="cap")
        unit = moe.MoE(wf, n_experts=16, top_k=2, width=32,
                       held=[1, 6], score="sigmoid", norm_topk=True)
        unit.input = Vector(x.copy())
        unit.initialize(device=XLADevice())
        assert unit._capacity == moe.HELD_SLACK * pairs * 2 // 16 \
            == pairs // 2
        if collapse:     # every token's top 2 are the experts held
            unit.weights.map_write()
            unit.weights.mem[0, [1, 6]] = 50.0
            unit.weights.unmap()
        unit.run()
        unit.output.map_read()
        unit.moe_stats.map_read()
        got, stats = np.array(unit.output.mem), np.array(unit.moe_stats.mem)
        unit.numpy_run()
        unit.output.map_read()
        return got, stats, np.array(unit.output.mem)

    whole, stats, oracle = run(False)
    assert 0 < stats[2 + 5] <= pairs // 2 and stats[2 + 5 + 2] == 0
    np.testing.assert_allclose(whole, oracle, atol=2e-5)
    short, stats, oracle = run(True)
    assert np.isfinite(oracle).all()          # the sum, were it computed
    assert np.isnan(short).all()
    assert (stats[2 + 5], stats[2 + 5 + 2]) == (pairs, pairs // 2)


# ----------------------------------------------------------------------
# rotary tables
# ----------------------------------------------------------------------
def _yarn_float64(dim, base, factor, original, beta_fast, beta_slow):
    """``transformers``' ``_compute_yarn_parameters`` (truncate on),
    transcribed term by term in float64."""
    def find_correction_dim(num_rotations):
        return (dim * math.log(original / (num_rotations * 2 * math.pi))
                ) / (2 * math.log(base))

    low = max(math.floor(find_correction_dim(beta_fast)), 0)
    high = min(math.ceil(find_correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    extrapolation = 1.0 / pos_freqs
    interpolation = 1.0 / (factor * pos_freqs)
    linear = (np.arange(dim // 2, dtype=np.float64) - low) / (high - low)
    extrapolation_factor = 1 - np.clip(linear, 0, 1)
    return interpolation * (1 - extrapolation_factor) \
        + extrapolation * extrapolation_factor


@pytest.mark.parametrize("dim,base,factor,original", [
    (64, 500000.0, 128.0, 8192), (8, 500000.0, 8.0, 16),
    (128, 10000.0, 4.0, 4096)])
def test_yarn_tables_against_a_float64_transcription(dim, base, factor,
                                                     original):
    yarn = {"factor": factor,
            "original_max_position_embeddings": original,
            "beta_fast": 32, "beta_slow": 1,
            "attention_factor": 1.4852030263919618}
    want = _yarn_float64(dim, base, factor, original, 32, 1)
    np.testing.assert_allclose(attention.yarn_inv_freq(dim, base, yarn),
                               want, rtol=1e-14)
    np.testing.assert_allclose(
        reference().inv_frequencies(dim, base, yarn)[0], want,
        rtol=1e-12)
    # the blend moves some frequencies and not others
    plain = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    assert np.isclose(want, plain).any() or dim == 8
    assert np.isclose(want, plain / factor).any()
    t = 48
    cos, sin = attention.rope_tables(np, t, dim, base, yarn)
    angle = np.arange(t, dtype=np.float64)[:, None] * want[None, :]
    np.testing.assert_allclose(cos, np.cos(angle) * 1.4852030263919618,
                               atol=1e-6)
    np.testing.assert_allclose(sin, np.sin(angle) * 1.4852030263919618,
                               atol=1e-6)
    # no attention_factor given: 0.1 ln(factor) + 1
    del yarn["attention_factor"]
    assert attention.yarn_attention_factor(yarn) == pytest.approx(
        0.1 * math.log(factor) + 1.0)
    if factor == 128.0:
        assert attention.yarn_attention_factor(yarn) == pytest.approx(
            1.4852030263919618, rel=1e-12)


def test_only_the_rotated_part_of_a_head_turns():
    rng = np.random.default_rng(2)
    x = rng.normal(0, 1, (2, 16, 3, 16)).astype(np.float32)
    cos, sin = attention.rope_tables(np, 16, 8, 10000.0)
    out = attention.apply_rope(np, x, cos, sin)
    np.testing.assert_array_equal(out[..., 8:], x[..., 8:])
    assert np.abs(out[:, 1:, :, :8] - x[:, 1:, :, :8]).max() > 0.1
    back = attention.apply_rope(np, out, cos, sin, inverse=True)
    np.testing.assert_allclose(back, x, atol=1e-5)
    rows = attention.apply_rope_rows(np, x.reshape(2, 16, 48), cos, sin, 3)
    np.testing.assert_allclose(rows, out.reshape(2, 16, 48), atol=1e-6)
