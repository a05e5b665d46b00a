"""Mesh-native Pallas kernels (round 6): flash attention and the
fused layer norm run per-shard under shard_map on multi-device
meshes instead of silently falling back to the XLA cores.

All kernel math runs the REAL kernels in interpret mode on the
virtual 8-device CPU mesh (the same pattern as
test_pallas_attention.py) and must match the plain-XLA oracle —
forward and every gradient, causal and not, partial tiles included.
The gate tests pin the fallback story: with
``engine.pallas_shard_map = False`` the kernels never engage
un-shard_mapped on a mesh (the GSPMD replicate-and-gather failure
mode), and illegal head dims fall back instead of raising.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from znicz_tpu.backends import XLADevice
from znicz_tpu.dummy import DummyUnit, DummyWorkflow
from znicz_tpu.memory import Vector
from znicz_tpu.ops import pallas_kernels
from znicz_tpu.ops.pallas_attention import flash_attention
from znicz_tpu.parallel import make_mesh
from znicz_tpu.parallel.axis import DATA_AXIS, MODEL_AXIS
from znicz_tpu.parallel.mesh import kernel_shard_spec, spec_divides
from znicz_tpu.parallel.ring_attention import (local_attention,
                                               sequence_sharded_attention)
from znicz_tpu.utils import prng
from znicz_tpu.utils.config import root


def _rand(shape, seed, scale=1.0):
    return jnp.asarray(scale * np.random.default_rng(seed)
                       .normal(0, 1, shape).astype(np.float32))


# ----------------------------------------------------------------------
# spec derivation (parallel/mesh.py — shared by kernels and ring)
# ----------------------------------------------------------------------
def test_kernel_shard_spec_derivation():
    dp = make_mesh()                       # (data=8, model=1)
    spec, axes = kernel_shard_spec(dp, 3)
    assert tuple(spec) == (DATA_AXIS, None, None)
    assert axes == (DATA_AXIS,)            # size-1 model axis ≠ reducer

    dm = make_mesh(n_data=4, n_model=2)
    spec, axes = kernel_shard_spec(dm, 3, model_shard_dim=1)
    assert tuple(spec) == (DATA_AXIS, MODEL_AXIS, None)
    assert axes == (DATA_AXIS, MODEL_AXIS)

    # model_shard_dim = 0 conflicts with the batch dim → batch yields
    spec, axes = kernel_shard_spec(dm, 2, model_shard_dim=0)
    assert tuple(spec) == (MODEL_AXIS, None)
    assert axes == (MODEL_AXIS,)

    # no mesh → fully unsharded
    spec, axes = kernel_shard_spec(None, 4)
    assert tuple(spec) == (None,) * 4 and axes == ()


def test_spec_divides():
    mesh = make_mesh(n_data=4, n_model=2)
    spec, _ = kernel_shard_spec(mesh, 3, model_shard_dim=1)
    assert spec_divides(mesh, (8, 6, 16), spec)
    assert not spec_divides(mesh, (6, 6, 16), spec)   # 6 % 4
    assert not spec_divides(mesh, (8, 5, 16), spec)   # 5 % 2


# ----------------------------------------------------------------------
# flash attention under shard_map ≡ XLA oracle (fwd + every grad)
# ----------------------------------------------------------------------
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("mesh_shape", [(8, 1), (4, 2)])
def test_flash_shard_map_matches_oracle(causal, mesh_shape):
    mesh = make_mesh(*mesh_shape)
    b, t, h, d = 8, 64, 2, 16
    q, k, v = (_rand((b, t, h, d), s) for s in (0, 1, 2))
    dy = _rand((b, t, h, d), 3)
    spec, _ = kernel_shard_spec(mesh, 4)
    # partial diagonal tiles: bq ≠ bk exercises the cross-boundary
    # causal mask inside the tile
    kw = dict(causal=causal, block_q=32, block_k=16, interpret=True,
              mesh=mesh, spec=spec)

    ref = local_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, **kw)
    np.testing.assert_allclose(out, ref, atol=2e-5)

    g_ref = jax.grad(
        lambda *a: jnp.vdot(local_attention(*a, causal=causal), dy),
        argnums=(0, 1, 2))(q, k, v)
    g_new = jax.grad(
        lambda *a: jnp.vdot(flash_attention(*a, **kw), dy),
        argnums=(0, 1, 2))(q, k, v)
    for name, a, b_ in zip("qkv", g_ref, g_new):
        np.testing.assert_allclose(b_, a, atol=5e-5,
                                   err_msg=f"grad d{name}")


def test_flash_shard_map_rejects_time_sharded_spec():
    mesh = make_mesh()
    q = _rand((8, 64, 2, 16), 0)
    with pytest.raises(ValueError, match="ring"):
        flash_attention(q, q, q, interpret=True, mesh=mesh,
                        spec=P(None, DATA_AXIS, None, None))


# ----------------------------------------------------------------------
# fused layer norm under shard_map ≡ the jnp composition
# ----------------------------------------------------------------------
def _ln_ref(x, g, b, eps=1e-5):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    y = (x - mu) / jnp.sqrt(var + eps) * g
    return y + b if b is not None else y


@pytest.mark.parametrize("with_beta", [True, False])
def test_layer_norm_shard_map_forward(with_beta):
    mesh = make_mesh()
    d = 16
    x = _rand((8, 520, d), 0)      # per-shard 520 rows: 512 + tail 8
    g = jnp.asarray(np.linspace(0.5, 1.5, d).astype(np.float32))
    b = (jnp.asarray(np.linspace(-0.2, 0.2, d).astype(np.float32))
         if with_beta else None)
    spec, _ = kernel_shard_spec(mesh, 3)
    y = pallas_kernels.layer_norm_forward(x, g, b, 1e-5,
                                          interpret=True,
                                          mesh=mesh, spec=spec)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(_ln_ref(x, g, b)),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("mesh_shape,msd", [((8, 1), None),
                                            ((4, 2), 1)])
def test_layer_norm_shard_map_backward(mesh_shape, msd):
    """dx per shard + γ/β grads psum'd across every row-sharding axis
    must equal autodiff of the composition — including on a
    (data × model) mesh with a ring-style time-sharded input."""
    mesh = make_mesh(*mesh_shape)
    d = 16
    x = _rand((8, 12, d), 1)
    e = _rand((8, 12, d), 2)
    g = jnp.asarray(np.linspace(0.5, 1.5, d).astype(np.float32))
    spec, axes = kernel_shard_spec(mesh, 3, model_shard_dim=msd)
    assert spec_divides(mesh, x.shape, spec)
    dx, gg, gb = pallas_kernels.layer_norm_backward(
        x, e, g, 1e-5, with_beta=True, interpret=True,
        mesh=mesh, spec=spec)
    ref_dx, ref_gg, ref_gb = jax.grad(
        lambda xx, ggm, bb: jnp.vdot(_ln_ref(xx, ggm, bb), e),
        argnums=(0, 1, 2))(x, g, jnp.zeros(d, jnp.float32))
    np.testing.assert_allclose(np.asarray(dx), np.asarray(ref_dx),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gg), np.asarray(ref_gg),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gb), np.asarray(ref_gb),
                               rtol=1e-4, atol=1e-4)


def test_layer_norm_shard_map_rejects_feature_sharded_spec():
    mesh = make_mesh()
    x = _rand((8, 4, 16), 0)
    g = jnp.ones(16, jnp.float32)
    with pytest.raises(ValueError, match="feature"):
        pallas_kernels.layer_norm_forward(
            x, g, None, 1e-5, interpret=True, mesh=mesh,
            spec=P(None, None, DATA_AXIS))


# ----------------------------------------------------------------------
# ring attention on a (data × model) mesh with the per-hop flash fold
# ----------------------------------------------------------------------
@pytest.mark.slow
@pytest.mark.parametrize("causal", [False, True])
def test_ring_per_hop_flash_on_data_model_mesh(causal):
    """The ring's block_k (per-hop flash) fold on a (data=2, model=4)
    mesh — batch sharded over data, time around the model-axis ring —
    must equal the local oracle (the spec now comes from the same
    kernel_shard_spec helper the Pallas kernels use)."""
    mesh = make_mesh(n_data=2, n_model=4)
    b, t, h, d = 4, 32, 2, 4
    q, k, v = (_rand((b, t, h, d), s) for s in (7, 8, 9))
    with jax.default_matmul_precision("highest"):
        ref = local_attention(q, k, v, causal=causal)
        got = sequence_sharded_attention(
            mesh, q, k, v, causal=causal, axis_name=MODEL_AXIS,
            block_k=4)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)
        ct = _rand(ref.shape, 10)
        _, vjp_ref = jax.vjp(
            lambda *a: local_attention(*a, causal=causal), q, k, v)
        _, vjp_got = jax.vjp(
            lambda *a: sequence_sharded_attention(
                mesh, *a, causal=causal, axis_name=MODEL_AXIS,
                block_k=4), q, k, v)
        for gr, gg in zip(vjp_ref(ct), vjp_got(ct)):
            np.testing.assert_allclose(np.asarray(gg), np.asarray(gr),
                                       rtol=3e-4, atol=3e-4)


# ----------------------------------------------------------------------
# unit gates: engagement, fallback switch, head-dim legality
# ----------------------------------------------------------------------
def _attention_unit(device, b=8, t=16, d=16, heads=2, **kw):
    from znicz_tpu.ops import attention
    prng.seed_all(5)
    wf = DummyWorkflow()
    x = np.random.default_rng(0).normal(
        0, 0.5, size=(b, t, d)).astype(np.float32)
    src = DummyUnit(wf, output=Vector(np.asarray(x), name="x"))
    unit = attention.MultiHeadAttention(wf, n_heads=heads, **kw)
    unit.link_attrs(src, ("input", "output"))
    unit.initialize(device=device)
    return unit


def _fake_tpu(monkeypatch):
    monkeypatch.setattr(pallas_kernels, "is_tpu_device",
                        lambda device: True)


def test_flash_gate_engages_shard_map_on_mesh(monkeypatch):
    _fake_tpu(monkeypatch)
    unit = _attention_unit(XLADevice(mesh=make_mesh()))
    assert unit._flash.runs
    assert unit._flash.mesh is not None
    assert tuple(unit._flash.spec) == (DATA_AXIS, None, None, None)


def test_flash_gate_fallback_switch_guards_gspmd(monkeypatch):
    """pallas_shard_map=False restores the conservative gate: the
    kernel must NOT engage un-shard_mapped on a multi-device mesh
    (the GSPMD replicate-and-gather failure mode, ADVICE round 5)."""
    _fake_tpu(monkeypatch)
    root.common.engine.pallas_shard_map = False
    unit = _attention_unit(XLADevice(mesh=make_mesh()))
    assert not unit._flash.runs
    assert unit._flash.mesh is None
    # single device is untouched by the switch
    assert _attention_unit(XLADevice())._flash.runs


def test_flash_gate_rejects_illegal_head_dim(monkeypatch):
    """dh not lane-friendly (dh % 8) falls back to the XLA core —
    no Mosaic trace crash (ADVICE round 5, the dh=1 to_sequence
    shape)."""
    _fake_tpu(monkeypatch)
    unit = _attention_unit(XLADevice(), d=16, heads=16)   # dh = 1
    assert not unit._flash.runs
    unit = _attention_unit(XLADevice(), d=16, heads=4)    # dh = 4
    assert not unit._flash.runs
    assert _attention_unit(XLADevice(), d=16, heads=2)._flash.runs


def test_ring_fold_gate_engages_kernel_on_capable_paths(monkeypatch):
    """seq_parallel on a model-axis mesh: the ring's per-hop fold is
    the flash KERNEL on TPU-capable paths (TPU device or interpret
    mode), attested via `_ring_fold` — the dryrun asserts the same."""
    _fake_tpu(monkeypatch)
    unit = _attention_unit(
        XLADevice(mesh=make_mesh(n_data=2, n_model=2)),
        seq_parallel=True)
    assert unit.ring_active
    assert unit._ring_fold == "pallas"
    assert unit._ring_block_q == 8          # t_local = 16/2


def test_ring_fold_gate_fallback_switch(monkeypatch):
    """engine.ring_pallas_fold=False restores the scan fold — the
    gated fallback the equality tests pin."""
    _fake_tpu(monkeypatch)
    root.common.engine.ring_pallas_fold = False
    unit = _attention_unit(
        XLADevice(mesh=make_mesh(n_data=2, n_model=2)),
        seq_parallel=True)
    assert unit.ring_active and unit._ring_fold == "scan"


def test_ring_fold_gate_rejects_kernel_illegal_shards(monkeypatch):
    """Per-SHARD legality (mesh.shard_shape geometry): t_local=4 (not
    lane-tileable) and dh=4 both fall back to the scan fold instead
    of crashing Mosaic at trace."""
    _fake_tpu(monkeypatch)
    unit = _attention_unit(
        XLADevice(mesh=make_mesh(n_data=1, n_model=4)),
        seq_parallel=True)                   # t_local = 16/4 = 4
    assert unit.ring_active and unit._ring_fold == "scan"
    unit = _attention_unit(
        XLADevice(mesh=make_mesh(n_data=2, n_model=2)),
        seq_parallel=True, heads=4)          # dh = 4
    assert unit.ring_active and unit._ring_fold == "scan"


def test_ring_fold_gate_non_tpu_keeps_scan(monkeypatch):
    """No TPU, no interpret: the ring keeps the portable scan fold
    (the non-TPU fallback behind engine.ring_pallas_fold=auto)."""
    unit = _attention_unit(
        XLADevice(mesh=make_mesh(n_data=2, n_model=2)),
        seq_parallel=True)
    assert unit.ring_active and unit._ring_fold == "scan"


def test_head_pack_gate(monkeypatch, caplog):
    """Where the kernels find a head's tiles, and how many heads share
    a program, is resolved from the shapes alone — the address in the
    projections' own layout at dh 64 (pairs) and dh 128, the head-major
    one where no lane-legal column block exists — and reported in the
    plan and its info line.  The retired
    ``engine.flash_head_pack`` steers nothing."""
    import logging

    from znicz_tpu.observe import metrics as obs_metrics
    _fake_tpu(monkeypatch)
    unit = _attention_unit(XLADevice(), d=32, heads=2)   # dh = 16
    assert unit._flash.runs
    assert (unit._flash.layout, unit._flash.head_pack) == ("head_major", 1)
    root.common.engine.flash_head_pack = True
    unit = _attention_unit(XLADevice(), d=32, heads=2)
    assert (unit._flash.layout, unit._flash.head_pack) == ("head_major", 1)
    with caplog.at_level(logging.INFO):
        unit = _attention_unit(XLADevice(), d=128, heads=2)  # dh = 64
    assert (unit._flash.layout, unit._flash.head_pack) == ("boundary", 2)
    assert "layout=boundary, head pack 2" in caplog.text
    assert unit._flash.n_heads == unit._flash.n_kv_heads == 2
    assert "query heads to a K/V head" not in unit._flash.line()
    # non-causal: the backward keeps its dq and dk/dv kernels
    assert unit._flash.backward == 2
    assert "backward passes 2" in caplog.text
    # the families the plan's line replaced are gone — but the
    # backward's passes and the dq it keeps in VMEM, which a probe and
    # an operator read as numbers (PR 55)
    for family in ("layout", "forward", "tiles"):
        assert "znicz_flash_%s{" % family \
            not in obs_metrics.REGISTRY.to_prometheus()
    assert obs_metrics.flash_backward(unit.name, "passes").value == 2
    assert obs_metrics.flash_backward(
        unit.name, "resident_dq_bytes").value == 0
    unit = _attention_unit(XLADevice(), d=256, heads=2)  # dh = 128
    assert (unit._flash.layout, unit._flash.head_pack) == ("boundary", 1)
    # an odd head count keeps one head per program, never raises
    unit = _attention_unit(XLADevice(), d=192, heads=3)
    assert (unit._flash.layout, unit._flash.head_pack) == ("head_major", 1)
    # the ring keeps the head-major address and takes the same pack
    unit = _attention_unit(
        XLADevice(mesh=make_mesh(n_data=2, n_model=2)),
        seq_parallel=True, d=128, heads=2)
    assert unit._ring_fold == "pallas" and unit._flash.layout is None


def test_causal_schedule_resolves_from_shapes_not_options(monkeypatch,
                                                          caplog):
    """The unit resolves its causal tile schedule from T and
    ``causal`` alone (pallas_attention.sub_tile_for): the grid blocks
    are the chooser's (1024 × 2048 at T 2048), the kernels walk compute
    sub-tiles inside them, and no engine option changes either — the retired
    ``engine.flash_causal_block`` included.  The split of the T × T
    square is in the plan and its info line."""
    import logging

    from znicz_tpu.observe import metrics as obs_metrics
    from znicz_tpu.ops import pallas_attention as pa
    _fake_tpu(monkeypatch)
    # T=2048: the LM cell's row (initialize never dispatches the
    # kernel, so the big T costs nothing here)
    with caplog.at_level(logging.INFO):
        unit = _attention_unit(XLADevice(), t=2048, causal=True)
    assert unit._flash.runs
    assert (unit._flash.block_q, unit._flash.block_k) == (1024, 2048)
    sub = unit._flash.sub_tile
    assert sub == pa.sub_tile_for(True, 1024, 2048) == (512, 512)
    tiles = unit._flash.tiles
    assert tiles == pa.causal_tile_counts(2048, 2048, 1024, 2048, *sub)
    assert tiles["executed_share"] <= 0.625
    assert f"{tiles['executed_share']:.4f} of T×T executed" in caplog.text
    assert "%d interior + %d crossing of %d = " % (
        tiles["interior"], tiles["crossing"], tiles["interior"]
        + tiles["crossing"] + tiles["skipped"]) in unit._flash.line()
    assert unit._flash.line() in caplog.text
    # one K tile holds every key a Q tile sees: the backward is ONE
    # kernel, from the shapes (pallas_attention.backward_passes)
    assert unit._flash.backward == pa.backward_passes(True, 2048, 2048) == 1
    assert "backward passes 1" in caplog.text
    # … and a row block of the forward meets its keys in ONE visit, so
    # it carries no softmax state (pallas_attention.forward_form)
    assert unit._flash.forward == pa.forward_form(2048, 1024, 2048, 16 // 2)
    assert unit._flash.forward.state == "none"
    assert "fwd_state: none, fwd_stats: lanes, fwd_scale: " in caplog.text
    assert unit._flash.forward.stats == "lanes"
    assert unit._flash.window is None and "window" not in unit._flash.line()
    # T 4096: the backward takes the two 2048-long K tiles whole, the
    # forward carries its state over them; past that the Q tiles' dq
    # wait in VMEM for their later K tiles, and past THAT room (seven
    # heads of 128 a K/V head at T 32,768: 112 MiB) a dq and a dk/dv
    # kernel
    assert _attention_unit(XLADevice(), t=4096,
                           causal=True)._flash.forward.state == "carried"
    whole = _attention_unit(XLADevice(), t=4096, causal=True)
    assert (whole._flash.backward, whole._flash.resident_dq) == (1, 0)
    deep = _attention_unit(XLADevice(), b=1, t=8192, causal=True)
    assert (deep._flash.backward, deep._flash.resident_dq) \
        == (1, 8192 * 8 * 4)     # two heads of 8
    assert "backward passes 1 (0.25 MiB of dq wait in VMEM)" \
        in deep._flash.line()
    assert obs_metrics.flash_backward(
        deep.name, "resident_dq_bytes").value == 8192 * 8 * 4
    past = _attention_unit(XLADevice(), b=1, t=32768, heads=7,
                           n_kv_heads=1, head_dim=128, causal=True)
    assert (past._flash.backward, past._flash.resident_dq) == (2, 0)
    assert pa.resident_dq_bytes(True, 32768, 2048, group=7) \
        == 112 * 2 ** 20 > pa.RESIDENT_DQ_VMEM
    # an option of that name steers nothing any more
    root.common.engine.flash_causal_block = 256
    again = _attention_unit(XLADevice(), t=2048, causal=True)
    assert (again._flash.block_q, again._flash.block_k) == (1024, 2048)
    assert again._flash.sub_tile == sub
    # non-causal units keep the single-body tile and the whole square
    unit = _attention_unit(XLADevice(), t=2048)
    assert unit._flash.sub_tile == (1024, 1024)
    assert unit._flash.tiles["executed_share"] == 1.0
    assert unit._flash.tiles["skipped"] == 0
    # off the kernel path there is no schedule to report
    monkeypatch.setattr(pallas_kernels, "is_tpu_device",
                        lambda device: False)
    unit = _attention_unit(XLADevice(), t=2048, causal=True)
    assert not unit._flash.runs and unit._flash.tiles is None


def _ln_unit(device, shape=(8, 16), model_shard_dim=None):
    from znicz_tpu.ops import layer_norm
    prng.seed_all(6)
    wf = DummyWorkflow()
    x = np.random.default_rng(1).normal(
        size=shape).astype(np.float32)
    vec = Vector(np.asarray(x), name="x")
    if model_shard_dim is not None:
        vec.model_shard_dim = model_shard_dim
    src = DummyUnit(wf, output=vec)
    unit = layer_norm.LayerNorm(wf)
    unit.link_attrs(src, ("input", "output"))
    unit.initialize(device=device)
    return unit


def test_ln_gate_engages_shard_map_on_mesh(monkeypatch):
    _fake_tpu(monkeypatch)
    unit = _ln_unit(XLADevice(mesh=make_mesh()))
    assert unit._pallas_ln and unit._ln_mesh is not None
    assert tuple(unit._ln_spec) == (DATA_AXIS, None)


def test_ln_gate_fallback_switch(monkeypatch):
    _fake_tpu(monkeypatch)
    root.common.engine.pallas_shard_map = False
    unit = _ln_unit(XLADevice(mesh=make_mesh()))
    assert not unit._pallas_ln
    assert _ln_unit(XLADevice())._pallas_ln


def test_ln_gate_time_sharded_input_engages(monkeypatch):
    """A ring-produced (time model-sharded) input now ENGAGES the
    kernel — time rides the model axis in the spec — instead of
    falling back (the old conservative gate)."""
    _fake_tpu(monkeypatch)
    unit = _ln_unit(XLADevice(mesh=make_mesh(n_data=2, n_model=4)),
                    shape=(8, 8, 16), model_shard_dim=1)
    assert unit._pallas_ln
    assert tuple(unit._ln_spec) == (DATA_AXIS, MODEL_AXIS, None)


def test_ln_gate_feature_sharded_input_falls_back(monkeypatch):
    _fake_tpu(monkeypatch)
    unit = _ln_unit(XLADevice(mesh=make_mesh(n_data=2, n_model=4)),
                    shape=(8, 8, 16), model_shard_dim=2)
    assert not unit._pallas_ln


# ----------------------------------------------------------------------
# end-to-end: engaged kernels inside the JitRegion + run_chunk scan
# ----------------------------------------------------------------------
def _seq_workflow(minibatch=16, t=16, d=16, heads=2):
    from znicz_tpu.loader.fullbatch import ArrayLoader
    from znicz_tpu.models.standard_workflow import StandardWorkflow
    rng = np.random.default_rng(9)
    n = 64
    x = rng.normal(0, 0.3, size=(n, t, d)).astype(np.float32)
    y = rng.integers(0, 3, size=n).astype(np.int32)
    span = t // 3
    for i in range(n):
        x[i, y[i] * span:(y[i] + 1) * span] += 1.0
    gd = {"learning_rate": 0.05, "gradient_moment": 0.9}
    prng.seed_all(17)
    wf = StandardWorkflow(
        name="shard_map_stack",
        loader_factory=lambda w: ArrayLoader(
            w, train_data=x[:48], train_labels=y[:48],
            valid_data=x[48:], valid_labels=y[48:],
            minibatch_size=minibatch),
        layers=[
            {"type": "attention", "->": {"n_heads": heads}, "<-": gd},
            {"type": "layer_norm", "->": {}, "<-": gd},
            {"type": "softmax", "->": {"output_sample_shape": 3},
             "<-": gd},
        ],
        decision_config={"max_epochs": 2})
    wf._max_fires = 10 ** 6
    return wf


def _train(engaged: bool):
    from znicz_tpu.utils.config import reset_root
    reset_root()
    if engaged:
        root.common.engine.flash_attention = True
        root.common.engine.pallas_layer_norm = True
        root.common.engine.pallas_interpret = True
    wf = _seq_workflow()
    wf.initialize(device=XLADevice(mesh=make_mesh()))
    attn, ln = wf.forwards[0], wf.forwards[1]
    assert attn._flash.runs == engaged
    assert (attn._flash.mesh is not None) == engaged
    assert bool(ln._pallas_ln) == engaged
    wf.run()
    attn.weights.map_read()
    ln.weights.map_read()
    return (attn.weights.mem.copy(), ln.weights.mem.copy(),
            wf.decision.min_validation_n_err)


@pytest.mark.slow
def test_engaged_kernels_train_equal_to_xla_on_dp_mesh():
    """The full tentpole claim: on the 8-device DP mesh, a
    JitRegion-traced train run with BOTH mesh-native kernels engaged
    (interpret mode) matches the XLA-cores run — same weights band,
    same validation error."""
    w_attn_x, w_ln_x, err_x = _train(engaged=False)
    w_attn_p, w_ln_p, err_p = _train(engaged=True)
    np.testing.assert_allclose(w_attn_p, w_attn_x, rtol=2e-3,
                               atol=2e-4)
    np.testing.assert_allclose(w_ln_p, w_ln_x, rtol=2e-3, atol=2e-4)
    assert err_x == err_p


def test_engaged_kernels_run_inside_run_chunk_scan():
    """The kernels must also trace inside the lax.scan chunk body
    (seq_bench's dispatch shape): one run_chunk(2) dispatch with both
    shard_map kernels engaged on the DP mesh."""
    from znicz_tpu.utils.config import reset_root
    reset_root()
    root.common.engine.flash_attention = True
    root.common.engine.pallas_layer_norm = True
    root.common.engine.pallas_interpret = True
    wf = _seq_workflow()
    wf.initialize(device=XLADevice(mesh=make_mesh()))
    assert wf.forwards[0]._flash.mesh is not None
    assert wf.forwards[1]._ln_mesh is not None
    region = wf._region_unit.region
    before = wf.forwards[0].weights.mem.copy()
    for _ in range(2):
        wf.loader.run()
    region.run_chunk(2)
    wf.forwards[0].weights.map_read()
    after = wf.forwards[0].weights.mem
    assert np.isfinite(after).all()
    assert np.abs(after - before).max() > 0   # the scan actually ran
