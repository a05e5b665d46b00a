"""The two-width flash kernels of ``ops/pallas_mla.py`` alone, at the
depth a long context gives them (PR 52): a K grid EIGHT tiles deep at a
toy tile in interpret mode — the forward's online softmax over eight K
tiles, ``dq`` accumulated over eight K tiles a Q tile, ``dk_nope`` /
``dv`` / ``dk_r`` over eight Q tiles a K tile and ``dk_r`` summed over
the pairs outside — against ``latent_attention_plain`` (K assembled in
full): the values and all five cotangents, from the ONE-pass backward
(``znicz_flash_bwd_mla``, PR 53: a pair's whole dq in VMEM, every score
tile computed once) and from the two-pass kernels it is past the VMEM
budget, bit for bit the same; the rule that picks
(``backward_passes``, from T and the widths); the statistics' layout
(``_STAT`` lanes a head, not a head's 128); the cotangents' dtype
(their operand's: no (B, T, H·128) array is f32 in HBM under bf16
operands).  ``tests/test_ling_reference.py`` holds the same kernels at
a 2 × 2 walk; ``tests/test_integrity.py`` compiles them through Mosaic
for a described v5e at T 16,384."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from znicz_tpu.ops import attention, pallas_mla

HEADS, TILE = 4, 128
OUTPUTS = ["o", "dq_nope", "dq_rope", "dk_nope",
           "dk_rope_summed_over_pairs", "dv"]


def rel(got, want) -> float:
    want = np.asarray(want, np.float32)
    return float(np.abs(np.asarray(got, np.float32) - want).max()
                 / (np.abs(want).max() + 1e-30))


def _rows(t: int, dtype):
    rng = np.random.default_rng(52)

    def draw(width):
        return jnp.asarray(rng.normal(size=(1, t, width)) * 0.3, dtype)
    return (draw(HEADS * 128), draw(HEADS * 64), draw(HEADS * 128),
            draw(64), draw(HEADS * 128)), draw(HEADS * 128)


def _both(rule, rows, weight):
    def run(*args):
        with jax.default_matmul_precision("highest"):
            return rule(*args), jax.grad(
                lambda *a: jnp.sum(rule(*a).astype(jnp.float32)
                                   * weight.astype(jnp.float32)),
                (0, 1, 2, 3, 4))(*args)
    o, grads = jax.jit(run)(*rows)
    return (o,) + tuple(grads)


def _kernels(rows, weight, monkeypatch, passes=None):
    monkeypatch.setattr(pallas_mla, "BLOCK", TILE)
    return _both(lambda *a: pallas_mla.latent_flash_attention(
        *a, interpret=True, passes=passes), rows, weight)


@pytest.fixture(scope="module")
def deep_calls():
    """T = 8 tiles (and 9: a length that is whole tiles but no power of
    two), f32: the kernels as the rule runs them (one backward pass),
    the plain core, and the kernels held to two passes — o and the five
    cotangents of each."""
    out = {}
    patch = pytest.MonkeyPatch()
    try:
        for tiles in (8, 9):
            rows, weight = _rows(tiles * TILE, jnp.float32)
            assert pallas_mla.backward_passes(tiles * TILE) == 1
            out[tiles] = (
                _kernels(rows, weight, patch),
                _both(lambda *a: attention.latent_attention_plain(
                    *a, HEADS), rows, weight),
                _kernels(rows, weight, patch, passes=2))
    finally:
        patch.undo()
    return out


@pytest.mark.parametrize("tiles", [8, 9])
@pytest.mark.parametrize("which", range(6), ids=OUTPUTS)
def test_a_k_grid_eight_tiles_deep_against_the_plain_core(deep_calls,
                                                          tiles, which):
    kernels, plain, _ = deep_calls[tiles]
    assert kernels[which].shape == plain[which].shape
    assert rel(kernels[which], plain[which]) < 2e-5
    # the shared key's cotangent is ONE key's, whatever the pairs
    assert kernels[4].shape == (1, tiles * TILE, 64)


@pytest.mark.parametrize("tiles", [8, 9])
@pytest.mark.parametrize("which", range(6), ids=OUTPUTS)
def test_one_backward_pass_is_the_two_bit_for_bit(deep_calls, tiles,
                                                  which):
    """K tiles ascending into a dq tile, Q tiles ascending into dk, dv
    and dk_r, in either form: the same sums in the same order."""
    one, plain, two = deep_calls[tiles]
    np.testing.assert_array_equal(np.asarray(one[which]),
                                  np.asarray(two[which]))
    assert rel(two[which], plain[which]) < 2e-5


def _pallas_calls(jaxpr) -> list:
    """(name, grid, output shapes and dtypes) of every ``pallas_call``
    of a jaxpr, nested ones too."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append((
                eqn.params["name"],
                tuple(eqn.params["grid_mapping"].grid),
                tuple((v.aval.shape, v.aval.dtype.name)
                      for v in eqn.outvars)))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _pallas_calls(sub)
    return found


@pytest.mark.parametrize("t,nope,rope,passes", [
    (2048, 128, 64, 1),         # xing_train_1of8: 3 MiB of dq a pair
    (4096, 128, 64, 1),         # ling_train_1of64: 6 MiB
    (16384, 128, 64, 1),        # kanana2_train_1of8: 24 MiB
    (16384 + 4096, 128, 64, 1),     # 30 MiB: the last that fits 32
    (16384 + 8192, 128, 64, 2),     # 36 MiB
    (32768, 128, 64, 2),        # 48 MiB
    (65536, 128, 64, 2),
    (16384, 192, 64, 1),        # wider keys: 32 MiB, the budget itself
    (16384 + 512, 192, 64, 2),
])
def test_the_backward_s_passes_are_read_from_the_shapes(t, nope, rope,
                                                        passes):
    assert pallas_mla.backward_passes(t, nope, rope) == passes
    # a pair's dq_nope and dq_rope over all of T, f32
    assert pallas_mla._resident_dq_bytes(t, nope, rope) \
        == t * 2 * (nope + rope) * 4
    if (nope, rope) == (128, 64):   # the widths the kernels tile
        assert pallas_mla.backward_passes(t) == passes


@pytest.mark.parametrize("passes,names", [
    (1, ["znicz_flash_bwd_mla"]),
    (2, ["znicz_flash_bwd_mla_dq", "znicz_flash_bwd_mla_dkv"])])
def test_the_backward_s_calls_by_passes(passes, names, monkeypatch):
    """One pass is ONE ``pallas_call`` with all five cotangents; past
    the budget the backward is the two calls it was before PR 53 — the
    same names, grids and outputs (a dq call of two, a dk/dv call of
    three), and no other."""
    monkeypatch.setattr(pallas_mla, "BLOCK", TILE)
    t, pairs, wide = 8 * TILE, HEADS // 2, HEADS * 128
    (qn, qr, kn, kr, v), do = _rows(t, jnp.bfloat16)
    lse = jnp.zeros((1, pairs, t, 2 * pallas_mla._STAT), jnp.float32)
    calls = _pallas_calls(jax.make_jaxpr(
        lambda *a: pallas_mla._backward(*a, True, passes))(
            qn, qr, kn, kr, v, do, lse, do).jaxpr)
    assert [name for name, _, _ in calls] == names
    assert all(grid == (1, pairs, 8, 8) for _, grid, _ in calls)
    dq = (((1, t, wide), "bfloat16"), ((1, t, pairs * 128), "bfloat16"))
    dkv = (((1, t, wide), "bfloat16"), ((1, t, wide), "bfloat16"),
           ((1, pairs, t, 128), "float32"))
    assert [outs for _, _, outs in calls] == (
        [dkv + dq] if passes == 1 else [dq, dkv])


def backward_of_the_parent(qn, qr, kn, kr, v, o, lse, do):
    """``pallas_mla._backward`` as PR 52 left it: the dq call, then the
    dk/dv call, over the module's kernels and specs."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    m = pallas_mla
    b, t, wide = qn.shape
    pairs = wide // (2 * m._LANES)
    bq = bk = min(m.BLOCK, t)
    steps = t // bq
    f32 = jnp.float32
    kr2 = m._twice(kr)
    q_side, k_side, pair, _, ins = m._specs(
        bq, bk, lambda i, j: i, lambda i, j: jnp.minimum(i, j))
    dqn, dqr = pl.pallas_call(
        m._dq_kernel, grid=(b, pairs, steps, steps), in_specs=ins,
        out_specs=(q_side(pair), q_side(m._LANES)),
        out_shape=(jax.ShapeDtypeStruct((b, t, wide), qn.dtype),
                   jax.ShapeDtypeStruct((b, t, pairs * m._LANES),
                                        qr.dtype)),
        scratch_shapes=[pltpu.VMEM((bq, pair), f32),
                        pltpu.VMEM((bq, m._LANES), f32)],
        compiler_params=m._PARAMS, interpret=True,
        name="znicz_flash_bwd_mla_dq",
    )(qn, qr, kn, kr2, v, o, do, lse)
    q_side, k_side, pair, _, ins = m._specs(
        bq, bk, lambda i, j: jnp.maximum(i, j), lambda i, j: i)
    dkn, dv, dkr = pl.pallas_call(
        m._dkv_kernel, grid=(b, pairs, steps, steps), in_specs=ins,
        out_specs=(k_side(pair), k_side(pair),
                   pl.BlockSpec((None, None, bk, m._LANES),
                                lambda b_, p, i, j: (b_, p, i, 0))),
        out_shape=(jax.ShapeDtypeStruct((b, t, wide), kn.dtype),
                   jax.ShapeDtypeStruct((b, t, wide), v.dtype),
                   jax.ShapeDtypeStruct((b, pairs, t, m._LANES), f32)),
        scratch_shapes=[pltpu.VMEM((bk, pair), f32),
                        pltpu.VMEM((bk, pair), f32),
                        pltpu.VMEM((bk, m._LANES), f32)],
        compiler_params=m._PARAMS, interpret=True,
        name="znicz_flash_bwd_mla_dkv",
    )(qn, qr, kn, kr2, v, o, do, lse)
    dkr = dkr.sum(axis=1)
    half = dkr.shape[-1] // 2
    return dqn, dqr, dkn, dkr[..., :half] + dkr[..., half:], dv


def test_two_passes_trace_the_parent_s_program(monkeypatch):
    """Past the budget the backward's jaxpr is, equation for equation,
    the one the two calls traced before the one pass existed (and one
    pass traces another)."""
    monkeypatch.setattr(pallas_mla, "BLOCK", TILE)
    t = 4 * TILE
    (qn, qr, kn, kr, v), do = _rows(t, jnp.bfloat16)
    lse = jnp.zeros((1, HEADS // 2, t, 2 * pallas_mla._STAT), jnp.float32)
    args = (qn, qr, kn, kr, v, do, lse, do)

    def text(passes):
        inner = jax.make_jaxpr(lambda *a: pallas_mla._backward(
            *a, True, passes))(*args).jaxpr.eqns[0].params["jaxpr"]
        return str(inner)

    parent = str(jax.make_jaxpr(backward_of_the_parent)(*args))
    assert text(2) == parent
    assert text(1) != parent


def test_past_the_budget_the_rule_runs_the_two_pass_kernels(monkeypatch):
    """A call whose dq does not fit the budget (here: a budget of one
    tile's worth) takes the two kernels THROUGH the rule, and its values
    are the one pass's."""
    monkeypatch.setattr(pallas_mla, "BLOCK", TILE)
    t = 4 * TILE
    rows, weight = _rows(t, jnp.float32)
    one = _kernels(rows, weight, monkeypatch)
    monkeypatch.setattr(pallas_mla, "RESIDENT_DQ_VMEM", TILE * 384 * 4)
    assert pallas_mla.backward_passes(t) == 2
    assert pallas_mla.backward_passes(TILE) == 1
    calls = _pallas_calls(jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(pallas_mla.latent_flash_attention(
            *a, interpret=True)), (0, 1, 2, 3, 4)))(*rows).jaxpr)
    assert [name for name, _, _ in calls] == [
        "znicz_flash_fwd_mla", "znicz_flash_bwd_mla_dq",
        "znicz_flash_bwd_mla_dkv"]
    two = _kernels(rows, weight, monkeypatch)
    for a, b in zip(one, two):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("head", range(HEADS))
def test_dk_rope_over_pairs_and_halves(head, monkeypatch):
    """The shared key's cotangent from the one call, with every rotary
    query but ONE head's zero: that head's own lanes of its pair's
    block — pair ``head // 2``, half ``head % 2`` — carry its sum
    through the sum over pairs and the fold of the halves outside."""
    monkeypatch.setattr(pallas_mla, "BLOCK", TILE)
    (qn, qr, kn, kr, v), weight = _rows(4 * TILE, jnp.float32)
    only = np.zeros((1, 1, HEADS, 1), np.float32)
    only[0, 0, head] = 1
    qr = (qr.reshape(1, -1, HEADS, 64) * only).reshape(qr.shape)
    rows = (qn, qr, kn, kr, v)
    got = _kernels(rows, weight, monkeypatch)
    plain = _both(lambda *a: attention.latent_attention_plain(*a, HEADS),
                  rows, weight)
    assert np.abs(np.asarray(plain[4])).max() > 0
    for which in (2, 4):    # dq_rope (zero off the head) and dk_rope
        assert rel(got[which], plain[which]) < 2e-5
    dq_rope = np.asarray(got[2]).reshape(-1, HEADS, 64)
    assert (np.abs(dq_rope).max(axis=(0, 2)) > 0).all()


def test_the_statistics_take_stat_lanes_a_head(monkeypatch):
    """``lse`` lies (B, pairs, T, 2 · _STAT) — 8 lanes a head, the
    value repeated over them — and is the log-sum-exp of the plain
    scores."""
    monkeypatch.setattr(pallas_mla, "BLOCK", TILE)
    t = 4 * TILE
    (qn, qr, kn, kr, v), _ = _rows(t, jnp.float32)
    o, lse = pallas_mla._forward(qn, qr, kn, kr, v, True)
    assert pallas_mla._STAT == 8
    assert lse.shape == (1, HEADS // 2, t, 2 * pallas_mla._STAT)
    assert lse.dtype == jnp.float32
    lse = np.asarray(lse).reshape(HEADS // 2, t, 2, pallas_mla._STAT)
    assert (lse == lse[..., :1]).all()
    q = np.concatenate([np.asarray(qn).reshape(t, HEADS, 128),
                        np.asarray(qr).reshape(t, HEADS, 64)], -1)
    k = np.concatenate([np.asarray(kn).reshape(t, HEADS, 128),
                        np.broadcast_to(np.asarray(kr)[0, :, None, :],
                                        (t, HEADS, 64))], -1)
    s = np.einsum("qhd,khd->hqk", q, k).astype(np.float64)
    s = np.where(np.tril(np.ones((t, t), bool))[None], s, -np.inf)
    want = np.log(np.exp(s - s.max(-1, keepdims=True)).sum(-1)) \
        + s.max(-1)
    got = lse[..., 0].transpose(0, 2, 1).reshape(HEADS, t)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("which", range(6), ids=OUTPUTS)
def test_bf16_operands_get_bf16_cotangents(which, monkeypatch):
    """Under bf16 operands every per-head cotangent leaves the ONE
    backward call in bf16 (the f32 accumulator — a pair's whole dq among
    them — cast at the one write) and is the f32 call's within bf16's
    rounding."""
    t = 8 * TILE
    rows32, weight = _rows(t, jnp.float32)
    rows16 = tuple(a.astype(jnp.bfloat16) for a in rows32)
    monkeypatch.setattr(pallas_mla, "BLOCK", TILE)
    raw = pallas_mla._backward(
        *rows16, *pallas_mla._forward(*rows16, True),
        weight.astype(jnp.bfloat16), True, 1)
    for grad, name in zip(raw, OUTPUTS[1:]):
        want = jnp.float32 if name.startswith("dk_rope") else jnp.bfloat16
        assert grad.dtype == want, name
    got = _kernels(rows16, weight, monkeypatch)
    exact = _both(lambda *a: attention.latent_attention_plain(*a, HEADS),
                  tuple(a.astype(jnp.float32) for a in rows16), weight)
    assert got[which].dtype == jnp.bfloat16
    assert rel(got[which], exact[which]) < 3e-2


def test_what_tiles_at_a_long_context():
    assert pallas_mla.kernel_legal(16384, 32, 128, 64, 128)
    assert pallas_mla.kernel_legal(8192, 32, 128, 64, 128)
    assert not pallas_mla.kernel_legal(16384 + 256, 32, 128, 64, 128)


@pytest.mark.parametrize("budget,passes,kernels", [
    (None, 1, "znicz_flash_fwd_mla / znicz_flash_bwd_mla kernels"),
    (384, 2, "znicz_flash_fwd_mla / znicz_flash_bwd_mla_dq / _dkv kernels"),
])
def test_the_unit_says_how_many_passes_its_backward_takes(
        budget, passes, kernels, monkeypatch, caplog):
    """``znicz_attention_latent{unit, stat="backward_passes"}`` beside
    the unit's other static gauges, the plan's line and the unit's info
    line: what the rule read from the shapes, per program."""
    import logging

    from znicz_tpu.backends import XLADevice
    from znicz_tpu.dummy import DummyUnit, DummyWorkflow
    from znicz_tpu.memory import Vector
    from znicz_tpu.observe import metrics as obs_metrics
    from znicz_tpu.utils.config import root
    root.common.engine.pallas_interpret = True
    root.common.engine.flash_attention = True
    if budget is not None:
        monkeypatch.setattr(pallas_mla, "RESIDENT_DQ_VMEM", budget)
    wf = DummyWorkflow()
    src = DummyUnit(wf, output=Vector(np.zeros((1, TILE, 64), np.float32),
                                      name="x"))
    unit = attention.MultiHeadAttention(
        wf, n_heads=2, causal=True, include_bias=False, pre_norm="rms",
        kv_latent=32, qk_nope=128, qk_rope=64, v_head_dim=128,
        rope={"theta": 10000})
    unit.link_attrs(src, ("input", "output"))
    with caplog.at_level(logging.INFO):
        unit.initialize(device=XLADevice())
    assert unit._flash.runs and unit._flash.backward_passes == passes
    assert obs_metrics.attention_latent(
        unit.name, "backward_passes").value == passes
    assert obs_metrics.attention_latent(unit.name, "qk_rope").value == 64
    line = unit._flash.line()
    assert line.startswith(kernels) and f"backward passes {passes}" in line
    assert line in caplog.text
    refused = pallas_mla.LatentPlan("no TPU", False, TILE, passes).line()
    assert "backward passes" not in refused and "no TPU" in refused
