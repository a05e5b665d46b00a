"""What lies between the q ‖ k ‖ v projection and the gated delta rule
as kernels (``pallas_delta.qkv_prep``: ``znicz_qkv_prep_fwd`` /
``_bwd``, PR 40), interpreted on the CPU:

1. the forward and the three cotangents (du, dtaps — nothing else has
   one) against ``_heads(_silu(causal_conv(…)))`` and ``jax.vjp`` of it:
   one row tile, several (the halo both ways across a tile boundary), a
   length that is not whole tiles, padding, two sequences, 2 and 4
   taps, heads of 128 × 128 and of 256 × 128; a sequence's first rows;
2. the rule that engages them: the kernels path AND heads that are
   whole lane tiles — at 96 × 192 ``xla_forward``'s jaxpr is the
   parent's, at 128 × 128 it holds exactly one forward call; the gauge
   says which;
3. the unit and its GD pair end to end, kernel path against plain path.
"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.test_delta_net import D, _NoGD, _build, _forward_of
from tests.test_laguna_reference import _params, _two_steps
from znicz_tpu.backends import XLADevice
from znicz_tpu.observe import metrics as obs_metrics
from znicz_tpu.ops import delta_net
from znicz_tpu.ops import pallas_delta as pd
from znicz_tpu.ops.moe import _silu
from znicz_tpu.utils.config import reset_root, root

EPS = 1e-6


# ======================================================================
# 1. the kernels against the plain form
# ======================================================================
def _plain(u, taps, h, dk, dv, pad):
    """The unit's own lines (``xla_forward``'s ``heads``), then the move
    to head-major and the padding the rule is handed."""
    unit = types.SimpleNamespace(n_heads=h, key_dim=dk, value_dim=dv,
                                 norm_eps=EPS)
    return tuple(
        jnp.pad(jnp.moveaxis(a, 2, 1), ((0, 0), (0, 0), (0, pad), (0, 0)))
        for a in delta_net.GatedDeltaNet._heads(
            unit, jnp, _silu(jnp, delta_net.causal_conv(jnp, u, taps))))


def _drawn(b, t, h, dk, dv, taps, pad, seed=0):
    rng = np.random.default_rng(seed)
    wide = h * (2 * dk + dv)
    return (jnp.asarray(rng.normal(size=(b, t, wide)), jnp.float32),
            jnp.asarray(0.5 * rng.normal(size=(wide, taps)), jnp.float32),
            tuple(jnp.asarray(rng.normal(size=(b, h, t + pad, d)),
                              jnp.float32) for d in (dk, dk, dv)))


#: name: (sequences, positions, padding, rows a grid step, taps)
WALKS = {
    "one_tile": (1, 64, 0, None, 4),
    "four_tiles": (1, 64, 0, 16, 4),
    "tiles_of_one_sub_tile": (1, 32, 0, 8, 4),
    "not_whole_tiles": (1, 40, 0, 16, 4),
    "padded": (1, 36, 12, 16, 4),
    "padded_one_tile": (1, 50, 14, None, 4),
    "two_sequences": (2, 48, 0, 16, 4),
    "two_sequences_two_taps": (2, 40, 8, 16, 2),
    "two_taps": (1, 64, 0, 32, 2),
}


@pytest.mark.parametrize("dk,dv", [(128, 128), (256, 128), (128, 256)])
@pytest.mark.parametrize("walk", sorted(WALKS))
def test_the_kernels_against_the_plain_form(walk, dk, dv):
    b, t, pad, rows, width = WALKS[walk]
    h = 2
    u, taps, weights = _drawn(b, t, h, dk, dv, width, pad)

    def kernels(u, taps):
        return pd.qkv_prep(u, taps, h, dk, dv, EPS, pad=pad, rows=rows,
                           interpret=True)

    def plain(u, taps):
        return _plain(u, taps, h, dk, dv, pad)

    got, back = jax.vjp(kernels, u, taps)
    want, plain_back = jax.vjp(plain, u, taps)
    for name, g, w in zip("qkv", got, want):
        assert g.shape == w.shape == (b, h, t + pad, dv if name == "v"
                                      else dk)
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5,
                                   err_msg=name)
        if pad:
            assert not np.asarray(g[:, :, t:]).any(), name
    # each cotangent alone, so that none hides behind a larger one
    for i, name in enumerate("qkv"):
        only = tuple(w if j == i else jnp.zeros_like(w)
                     for j, w in enumerate(weights))
        for what, g, w in zip(("du", "dtaps"), back(only),
                              plain_back(only)):
            scale = float(jnp.abs(w).max())
            np.testing.assert_allclose(
                np.asarray(g) / scale, np.asarray(w) / scale, atol=1e-5,
                err_msg=f"{what} from d{name}")


@pytest.mark.parametrize("width", [2, 4])
def test_a_sequence_s_first_rows_see_zeros_before_them(width):
    """Row 0 meets only the last tap; with two sequences the second's
    first rows see nothing of the first's last."""
    h, dk, dv = 1, 128, 128
    u, taps, _ = _drawn(2, 16, h, dk, dv, width, 0, seed=3)
    q, k, v = pd.qkv_prep(u, taps, h, dk, dv, EPS, rows=8, interpret=True)
    first = _silu(jnp, u[:, 0] * taps[:, width - 1])
    np.testing.assert_allclose(v[:, 0, 0], first[:, 2 * dk:], rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(
        k[:, 0, 0], delta_net.l2_normalize(jnp, first[:, dk:2 * dk], EPS),
        rtol=1e-5, atol=1e-6)
    # the second sequence alone gives the same rows
    alone = pd.qkv_prep(u[1:], taps, h, dk, dv, EPS, rows=8,
                        interpret=True)
    for both, one in zip((q, k, v), alone):
        np.testing.assert_array_equal(both[1:], one)


def test_heads_that_are_not_whole_lane_tiles_are_refused_by_name():
    assert pd.prep_legal(128, 128) and pd.prep_legal(256, 128)
    assert not pd.prep_legal(96, 192) and not pd.prep_legal(128, 192)
    with pytest.raises(ValueError, match="96 x 192"):
        pd.qkv_prep(jnp.zeros((1, 8, 2 * 384)), jnp.zeros((768, 4)), 2,
                    96, 192, EPS)


# ======================================================================
# 2. the rule that engages them
# ======================================================================
@pytest.fixture
def engine():
    reset_root()
    yield root.common.engine
    reset_root()


def _kernels_on(engine):
    engine.pallas_interpret = True
    engine.delta_scan_kernel = True


def _unit(dk, dv, t=64, **options):
    options = dict(dict(n_heads=2, key_dim=dk, value_dim=dv, chunk=16,
                        norm_eps=EPS), **options)
    unit, _ = _forward_of(
        lambda wf: delta_net.GatedDeltaNet(wf, name="mixer", **options),
        np.zeros((1, t, D), np.float32))
    return unit


def _nested(jaxpr):
    """Every equation of a jaxpr, those of nested jaxprs too."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) \
                    else (value,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _nested(inner)


def _calls(jaxpr) -> list:
    """Names of every jitted call and kernel in a jaxpr."""
    return [eqn.params["name"] for eqn in _nested(jaxpr)
            if eqn.primitive.name in ("pjit", "jit", "pallas_call")]


def _equations(jaxpr) -> int:
    return sum(1 for _ in _nested(jaxpr))


CHANNEL = dict(decay="channel", lower_bound=-5.0, gate="sigmoid")
#: (top-level, all nested) equations of ``xla_forward``'s jaxpr at 2
#: heads of 96 × 192 on the kernels path, T 64 and T 40 (padded),
#: counted AT THE PARENT (commit 98de625) with this file's own helpers:
#: the program of a head that is not whole lane tiles does not move.
#: (The nested counts are PR 41's: the chunk kernels' bodies — nested
#: here — changed the form of their products with a 0/1 matrix, +34,
#: +18, +29, +17 equations; at the parent 479, 363, 489, 375.  The
#: top-level counts, which are the unit's own program, are the
#: parent's.)
PARENT = {("head", 64): (104, 513), ("head", 40): (110, 381),
          ("channel", 64): (57, 518), ("channel", 40): (63, 392)}


#: the same of the unit's forward-AND-backward program (``jax.vjp`` of
#: ``xla_forward`` pulled back from ones, T 64), which holds the
#: ``znicz_*_chunk_bwd`` and ``znicz_*_state_bwd`` kernels' bodies too, at
#: 96 × 192 and at 128 × 128 (Ling's shape, the prep path), counted AT
#: THE PARENT (commit 895744d, PR 41) with this file's own helpers
#: before PR 42 put one scaffold under the two decay shapes: a scaffold
#: that adds or drops an equation either way fails here.
PARENT_BOTH_WAYS = {("head", 96, 192): (307, 1255),
                    ("head", 128, 128): (164, 1659),
                    ("channel", 96, 192): (206, 1284),
                    ("channel", 128, 128): (152, 1644)}


def _both_ways(forward):
    def run(*args):
        out, back = jax.vjp(forward, *args)
        return out, back(jnp.ones_like(out))
    return run


@pytest.mark.parametrize(
    "decay,t,head,both_ways",
    [(decay, t, (96, 192), False) for decay, t in sorted(PARENT)]
    + [(decay, 64, (dk, dv), True)
       for decay, dk, dv in sorted(PARENT_BOTH_WAYS)])
def test_at_96_by_192_the_program_is_the_parent_s(decay, t, head,
                                                  both_ways, engine):
    _kernels_on(engine)
    unit = _unit(*head, t=t, **(CHANNEL if decay == "channel" else {}))
    prep = pd.prep_legal(*head)
    assert unit._kernels and unit._prep == prep
    assert obs_metrics.delta_scan("mixer", "prep_path").value == prep
    assert obs_metrics.delta_scan("mixer", "chunk_path").value == 1.0
    forward = unit.xla_forward.__wrapped__
    jaxpr = jax.make_jaxpr(_both_ways(forward) if both_ways else forward)(
        *unit.forward_args()).jaxpr
    assert (len(jaxpr.eqns), _equations(jaxpr)) == (
        PARENT_BOTH_WAYS[(decay, *head)] if both_ways
        else PARENT[decay, t])
    kernels = [name for name in _calls(jaxpr) if name.startswith("znicz")]
    assert ("qkv_prep" in " ".join(kernels)) == prep
    if both_ways:   # each shape's four, and none of the other's
        mine, other = ("kda", "gdr") if decay == "channel" \
            else ("gdr", "kda")
        state = "kda_state" if decay == "channel" else "delta_state"
        assert [name for name in kernels if "qkv_prep" not in name] == [
            f"znicz_{mine}_chunk_fwd", f"znicz_{state}_fwd",
            f"znicz_{state}_bwd", f"znicz_{mine}_chunk_bwd"]
        assert other not in " ".join(kernels)


@pytest.mark.parametrize("decay", ["head", "channel"])
def test_at_128_by_128_on_the_kernels_path_one_forward_call(decay,
                                                            engine):
    _kernels_on(engine)
    unit = _unit(128, 128, **(CHANNEL if decay == "channel" else {}))
    assert unit._prep
    assert obs_metrics.delta_scan("mixer", "prep_path").value == 1.0
    jaxpr = jax.make_jaxpr(unit.xla_forward.__wrapped__)(
        *unit.forward_args()).jaxpr
    names = _calls(jaxpr)
    assert names.count("_qkv_prep_forward") == 1
    assert "_qkv_prep_backward" not in names
    # one body, a call a column range; no name a reader of the cell's
    # kernels would catch
    kernels = [name for name in names if "qkv_prep" in name
               and not name.startswith("_")]
    assert kernels == ["znicz_qkv_prep_fwd"] * 3
    for caught in ("kda", "flash", "_mla", "gmm", "gdr", "delta_state"):
        assert caught not in "znicz_qkv_prep_fwd znicz_qkv_prep_bwd"
    # the move to head-major is left to log α, β and o
    assert "prep_path" in obs_metrics.delta_scan.__doc__
    moves = sum(eqn.primitive.name == "transpose" for eqn in jaxpr.eqns)
    assert moves == 3, moves


def test_off_the_kernels_path_the_gauge_reads_zero(engine):
    unit = _unit(128, 128)
    assert not unit._kernels and not unit._prep
    assert obs_metrics.delta_scan("mixer", "prep_path").value == 0.0


# ======================================================================
# 3. the unit and its GD pair, kernel path against plain path
# ======================================================================
def _trained(make, x, err, params=None):
    fwd, gd_u = _build(XLADevice(), x, make, delta_net.GDGatedDeltaNet,
                       params=params)
    drawn = _params(fwd)
    return fwd, drawn, _two_steps(fwd, gd_u, err)


@pytest.mark.parametrize("decay", ["head", "channel"])
@pytest.mark.parametrize("t", [32, 24])
def test_unit_and_gd_pair_kernel_path_against_plain_path(decay, t,
                                                         engine):
    options = dict(n_heads=2, key_dim=128, value_dim=128, conv_kernel=4,
                   residual=True, norm_eps=EPS, chunk=16, pre_norm="rms",
                   **(CHANNEL if decay == "channel"
                      else dict(allow_neg_eigval=True)))

    def make(wf):
        return delta_net.GatedDeltaNet(wf, **options)

    rng = np.random.default_rng(0)
    x = rng.normal(0, 1.0, (2, t, D)).astype(np.float32)
    err = rng.normal(0, 0.1, (2, t, D)).astype(np.float32)
    plain, drawn, want = _trained(make, x, err)
    assert not plain._prep
    _kernels_on(engine)
    unit, _, got = _trained(make, x, err, params=drawn)
    assert unit._prep and unit._kernels
    for key, value in want.items():
        np.testing.assert_allclose(got[key], value, rtol=2e-3, atol=1e-4,
                                   err_msg=key)
    for attr in drawn:            # and every parameter MOVED
        assert np.abs(want[attr] - drawn[attr]).max() > 0, attr


def test_the_forward_alone_takes_the_forward_kernel(engine):
    """``xla_run`` untraced (no pullback asked for) on the kernel path
    against the plain path."""
    rng = np.random.default_rng(1)
    x = rng.normal(0, 1.0, (2, 40, D)).astype(np.float32)
    options = dict(n_heads=2, key_dim=128, value_dim=128, chunk=16,
                   norm_eps=EPS, residual=True, **CHANNEL)

    def make(wf):
        return delta_net.GatedDeltaNet(wf, **options)

    plain, want = _forward_of(make, x)
    drawn = _params(plain)
    _kernels_on(engine)
    fwd, _ = _build(XLADevice(), x, make, lambda wf, **kw: _NoGD(),
                    params=drawn)
    assert fwd._prep
    fwd.run()
    assert fwd._traced_vjp is None
    fwd.output.map_read()
    np.testing.assert_allclose(fwd.output.mem, np.asarray(want),
                               rtol=2e-3, atol=1e-4)
