"""Multi-head attention units: oracle↔XLA agreement, analytic-vs-vjp
gradients, the sequence-parallel ring path on the virtual mesh, and
end-to-end training through StandardWorkflow."""

import numpy as np
import pytest

from znicz_tpu.backends import NumpyDevice, XLADevice
from znicz_tpu.dummy import DummyUnit, DummyWorkflow
from znicz_tpu.memory import Vector
from znicz_tpu.ops import attention
from znicz_tpu.utils import prng

B, T, D, H = 2, 8, 12, 3


def build(device, x, gd=False, **kwargs):
    prng.seed_all(5)
    wf = DummyWorkflow()
    src = DummyUnit(wf, output=Vector(np.asarray(x), name="x"))
    fwd = attention.MultiHeadAttention(wf, n_heads=H, **kwargs)
    fwd.link_attrs(src, ("input", "output"))
    fwd.initialize(device=device)
    if not gd:
        return fwd
    err = Vector(np.zeros((x.shape[0], x.shape[1], x.shape[2]),
                          np.float32), name="err")
    unit = attention.GDMultiHeadAttention(
        wf, learning_rate=0.05, gradient_moment=0.9)
    unit.forward_unit = fwd
    unit.link_attrs(fwd, "input", "output", "weights", "bias")
    unit.err_output = err
    unit.initialize(device=device)
    return fwd, unit


def _rand(seed=0):
    rng = np.random.default_rng(seed)
    return rng.normal(0, 0.5, size=(B, T, D)).astype(np.float32)


@pytest.mark.parametrize("causal", [False, True])
def test_forward_oracle_agreement(causal):
    x = _rand()
    np_u = build(NumpyDevice(), x, causal=causal)
    xla_u = build(XLADevice(), x, causal=causal)
    for src, dst in ((np_u.weights, xla_u.weights),
                     (np_u.bias, xla_u.bias),
                     (np_u.weights_out, xla_u.weights_out),
                     (np_u.bias_out, xla_u.bias_out)):
        dst.reset(src.mem.copy())
        dst.initialize(xla_u.device)
    np_u.run()
    xla_u.run()
    np_u.output.map_read()
    xla_u.output.map_read()
    np.testing.assert_allclose(np_u.output.mem, xla_u.output.mem,
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_backward_oracle_vs_vjp(causal):
    """The analytic numpy backward and jax.vjp agree on every
    gradient (weights updated identically from identical errors)."""
    x = _rand(1)
    err = np.random.default_rng(2).normal(
        0, 0.1, size=(B, T, D)).astype(np.float32)
    results = {}
    for device in (NumpyDevice(), XLADevice()):
        fwd, gd_u = build(device, x, gd=True, causal=causal)
        if results:  # copy the numpy init into the XLA run
            (w0, wo0, b0, bo0) = results["init"]
            for vec, arr in ((fwd.weights, w0), (fwd.weights_out, wo0),
                             (fwd.bias, b0), (fwd.bias_out, bo0)):
                vec.reset(arr.copy())
                vec.initialize(device)
        else:
            results["init"] = (fwd.weights.mem.copy(),
                               fwd.weights_out.mem.copy(),
                               fwd.bias.mem.copy(),
                               fwd.bias_out.mem.copy())
        fwd.run()
        gd_u.err_output.reset(err.copy())
        gd_u.err_output.initialize(device)
        gd_u.run()
        for vec in (fwd.weights, fwd.weights_out, fwd.bias,
                    fwd.bias_out, gd_u.err_input):
            vec.map_read()
        results[type(device).__name__] = (
            fwd.weights.mem.copy(), fwd.weights_out.mem.copy(),
            fwd.bias.mem.copy(), fwd.bias_out.mem.copy(),
            gd_u.err_input.mem.astype(np.float32).copy())
    for a, b in zip(results["NumpyDevice"], results["XLADevice"]):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=1e-4)


def test_numeric_gradient():
    """err_input from the analytic oracle matches finite differences
    of a scalar loss through the forward."""
    x = _rand(3)[:1, :4]  # tiny for FD cost
    np_u, gd_u = build(NumpyDevice(), x, gd=True)
    np_u.run()
    # loss = sum(y * c)
    c = np.random.default_rng(4).normal(
        size=np_u.output.shape).astype(np.float32)
    gd_u.err_output.reset(c.copy())
    gd_u.learning_rate = 0.0  # no weight update; just err_input
    gd_u.gradient_moment = 0.0
    gd_u.run()
    gd_u.err_input.map_read()
    analytic = gd_u.err_input.mem.copy()
    eps = 1e-3
    fd = np.zeros_like(x)
    for idx in np.ndindex(*x.shape):
        for sign in (1, -1):
            xp = x.copy()
            xp[idx] += sign * eps
            np_u.input.reset(xp)
            np_u.run()
            np_u.output.map_read()
            fd[idx] += sign * float((np_u.output.mem * c).sum())
    fd /= 2 * eps
    np.testing.assert_allclose(analytic, fd, rtol=2e-2, atol=2e-3)


def test_seq_parallel_matches_local():
    """Ring attention over the mesh's model axis produces the same
    output as the local path (the unit falls back to local when the
    mesh has no model axis)."""
    from znicz_tpu.parallel import make_mesh

    x = _rand(6)
    local = build(XLADevice(), x, causal=True)
    mesh = make_mesh(n_data=2, n_model=4)
    ring = build(XLADevice(mesh=mesh), x, causal=True,
                 seq_parallel=True)
    assert ring.ring_active, "mesh has a model axis; ring must engage"
    assert ring.output.model_shard_dim == 1
    for src, dst in ((local.weights, ring.weights),
                     (local.bias, ring.bias),
                     (local.weights_out, ring.weights_out),
                     (local.bias_out, ring.bias_out)):
        dst.reset(np.asarray(src).copy())
        dst.initialize(ring.device)
    local.run()
    ring.run()
    # DP composes with SP: the ring's shard_map spec threads the data
    # axis, so the output stays batch-sharded (2 shards) while the
    # time axis rides the model ring (4 shards)
    out_shard = ring.output.devmem.sharding.shard_shape(
        ring.output.devmem.shape)
    assert out_shard == (B // 2, T // 4, D), out_shard
    local.output.map_read()
    ring.output.map_read()
    np.testing.assert_allclose(np.asarray(ring.output.mem, np.float32),
                               np.asarray(local.output.mem, np.float32),
                               rtol=1e-4, atol=1e-5)


def test_trains_in_standard_workflow():
    """'attention' layer type end to end: classify which third of the
    sequence holds the marker token (needs cross-position mixing —
    attention solves it, and the loss must actually fall)."""
    from znicz_tpu.loader.fullbatch import ArrayLoader
    from znicz_tpu.models.standard_workflow import StandardWorkflow

    rng = np.random.default_rng(9)
    n, t, d, n_classes = 96, 9, 8, 3
    x = rng.normal(0, 0.3, size=(n, t, d)).astype(np.float32)
    y = rng.integers(0, n_classes, size=n).astype(np.int32)
    marker = np.ones(d, np.float32) * 2.0
    for i in range(n):
        x[i, y[i] * 3 + rng.integers(0, 3)] += marker
    prng.seed_all(11)
    wf = StandardWorkflow(
        name="attn_wf",
        loader_factory=lambda w: ArrayLoader(
            w, train_data=x[:72], train_labels=y[:72],
            valid_data=x[72:], valid_labels=y[72:], minibatch_size=24),
        layers=[
            {"type": "attention", "->": {"n_heads": 2},
             "<-": {"learning_rate": 0.05, "gradient_moment": 0.9}},
            {"type": "softmax", "->": {"output_sample_shape": n_classes},
             "<-": {"learning_rate": 0.05, "gradient_moment": 0.9}},
        ],
        decision_config={"max_epochs": 25})
    wf._max_fires = 10 ** 6
    wf.initialize(device=XLADevice())
    wf.run()
    assert wf.decision.min_validation_n_err_pt <= 25.0


@pytest.mark.slow
def test_seq_parallel_backward_matches_local():
    """Training through the ring (jax.vjp differentiates the
    shard_map/ppermute loop) must update weights and propagate
    err_input identically to the local-attention path."""
    from znicz_tpu.parallel import make_mesh

    x = _rand(12)
    err = np.random.default_rng(13).normal(
        0, 0.1, size=(B, T, D)).astype(np.float32)
    results = {}
    init = None
    for mode in ("local", "ring"):
        if mode == "ring":
            device = XLADevice(mesh=make_mesh(n_data=2, n_model=4))
        else:
            device = XLADevice()
        fwd, gd_u = build(device, x, gd=True, causal=True,
                          seq_parallel=(mode == "ring"))
        if mode == "ring":
            assert fwd.ring_active
        if init is None:
            init = (fwd.weights.mem.copy(), fwd.weights_out.mem.copy(),
                    fwd.bias.mem.copy(), fwd.bias_out.mem.copy())
        else:
            for vec, arr in zip((fwd.weights, fwd.weights_out,
                                 fwd.bias, fwd.bias_out), init):
                vec.reset(arr.copy())
                vec.initialize(device)
        fwd.run()
        gd_u.err_output.reset(err.copy())
        gd_u.err_output.initialize(device)
        gd_u.run()
        for vec in (fwd.weights, fwd.weights_out, fwd.bias,
                    fwd.bias_out, gd_u.err_input):
            vec.map_read()
        results[mode] = (
            fwd.weights.mem.copy(), fwd.weights_out.mem.copy(),
            fwd.bias.mem.copy(), fwd.bias_out.mem.copy(),
            np.asarray(gd_u.err_input.mem, np.float32).copy())
    for a, b in zip(results["local"], results["ring"]):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=1e-4)


def test_attention_seq_sample():
    """The zoo sample builds and trains through the CLI protocol."""
    from znicz_tpu.models.samples import attention_seq
    from znicz_tpu.utils.config import root

    prng.seed_all(17)
    prev = root.attention_seq.max_epochs
    root.attention_seq.max_epochs = 12
    try:
        wf = attention_seq.build()
        wf.initialize(device=XLADevice())
        wf.run()
    finally:
        root.attention_seq.max_epochs = prev
    assert wf.decision.min_validation_n_err_pt <= 20.0


def test_attention_export_roundtrip(tmp_path):
    """Export must carry BOTH attention parameter pairs (a fresh
    weights_out would silently corrupt served predictions)."""
    from znicz_tpu.export import ExportedModel, export_forward
    from znicz_tpu.loader.fullbatch import ArrayLoader
    from znicz_tpu.models.standard_workflow import StandardWorkflow

    rng = np.random.default_rng(21)
    x = rng.normal(0, 0.5, size=(48, 6, 8)).astype(np.float32)
    y = rng.integers(0, 3, size=48).astype(np.int32)
    prng.seed_all(22)
    wf = StandardWorkflow(
        name="attn_export",
        loader_factory=lambda w: ArrayLoader(
            w, train_data=x, train_labels=y, minibatch_size=16),
        layers=[{"type": "attention", "->": {"n_heads": 2},
                 "<-": {"learning_rate": 0.05}},
                {"type": "softmax", "->": {"output_sample_shape": 3},
                 "<-": {"learning_rate": 0.05}}],
        decision_config={"max_epochs": 2})
    wf._max_fires = 10 ** 6
    wf.initialize(device=XLADevice())
    wf.run()
    path = export_forward(wf, str(tmp_path / "attn.npz"))
    served = ExportedModel.load(path, device=XLADevice())
    batch = x[:8]
    probs = np.asarray(served(batch))
    # reference: the workflow's own forward math on the same weights
    fwd = wf.forwards[0]
    for vec in (fwd.weights, fwd.bias, fwd.weights_out, fwd.bias_out,
                wf.forwards[1].weights, wf.forwards[1].bias):
        vec.map_read()
    y1, _ = fwd._forward_np(batch)
    logits = y1.reshape(8, -1) @ wf.forwards[1].weights.mem \
        + wf.forwards[1].bias.mem
    e = np.exp(logits - logits.max(axis=1, keepdims=True))
    expected = e / e.sum(axis=1, keepdims=True)
    np.testing.assert_allclose(probs, expected, rtol=1e-3, atol=1e-4)


def test_export_refuses_missing_params(tmp_path):
    """A bundle lacking a parameter the rebuilt unit random-fills
    (e.g. pre-EXPORT_PARAMS attention exports) must refuse to serve,
    not silently substitute noise."""
    import io
    import json

    from znicz_tpu.export import ExportedModel, export_forward
    from znicz_tpu.loader.fullbatch import ArrayLoader
    from znicz_tpu.models.standard_workflow import StandardWorkflow

    rng = np.random.default_rng(23)
    x = rng.normal(size=(32, 4, 8)).astype(np.float32)
    y = rng.integers(0, 2, size=32).astype(np.int32)
    prng.seed_all(24)
    wf = StandardWorkflow(
        name="attn_trunc",
        loader_factory=lambda w: ArrayLoader(
            w, train_data=x, train_labels=y, minibatch_size=16),
        layers=[{"type": "attention", "->": {"n_heads": 2},
                 "<-": {"learning_rate": 0.05}},
                {"type": "softmax", "->": {"output_sample_shape": 2},
                 "<-": {"learning_rate": 0.05}}],
        decision_config={"max_epochs": 1})
    wf._max_fires = 10 ** 6
    wf.initialize(device=XLADevice())
    wf.run()
    path = export_forward(wf, str(tmp_path / "full.npz"))
    # rewrite the bundle WITHOUT the attention out-projection arrays —
    # the shape of a pre-EXPORT_PARAMS export
    with np.load(path) as bundle:
        arrays = {k: bundle[k] for k in bundle.files
                  if not k.endswith(("weights_out", "bias_out"))}
    trunc = str(tmp_path / "truncated.npz")
    buf = io.BytesIO()
    np.savez_compressed(buf, **arrays)
    with open(trunc, "wb") as fh:
        fh.write(buf.getvalue())
    served = ExportedModel.load(trunc, device=XLADevice())
    with pytest.raises(ValueError, match="missing from the bundle"):
        served(x[:4])


def test_positional_encoding():
    """PE forward adds the exact sinusoid table (oracle == XLA) and
    the backward passes errors through untouched."""
    from znicz_tpu.ops import pos_encoding

    x = _rand(31)
    np_u = build_pe(NumpyDevice(), x)
    xla_u = build_pe(XLADevice(), x)
    np_u.run()
    xla_u.run()
    np_u.output.map_read()
    xla_u.output.map_read()
    table = pos_encoding.sinusoid_table(T, D)
    np.testing.assert_allclose(np_u.output.mem, x + table, rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(xla_u.output.mem, np.float32), x + table,
        rtol=1e-4, atol=1e-5)
    # backward: identity pass-through of the error cotangent
    err = _rand(32)
    gd_u = pos_encoding.GDPositionalEncoding(np_u.workflow)
    gd_u.forward_unit = np_u
    gd_u.link_attrs(np_u, "input", "output")
    gd_u.err_output = Vector(err.copy(), name="err", batch_major=True)
    gd_u.initialize(device=NumpyDevice())
    gd_u.run()
    gd_u.err_input.map_read()
    np.testing.assert_array_equal(gd_u.err_input.mem, err)


def build_pe(device, x):
    from znicz_tpu.ops import pos_encoding

    wf = DummyWorkflow()
    src = DummyUnit(wf, output=Vector(np.asarray(x), name="x"))
    unit = pos_encoding.PositionalEncoding(wf)
    unit.link_attrs(src, ("input", "output"))
    unit.initialize(device=device)
    return unit


def test_pe_attention_trains_on_positional_task():
    """Class = which third of the sequence carries the energy bump;
    without positions the attention pool is permutation-invariant, so
    passing this bound certifies PE actually injects position."""
    from tests.conftest import positional_task_workflow

    gd = {"learning_rate": 0.05, "gradient_moment": 0.9}
    wf = positional_task_workflow(
        [{"type": "pos_encoding", "->": {}},
         {"type": "attention", "->": {"n_heads": 2}, "<-": gd},
         {"type": "softmax", "->": {"output_sample_shape": 3},
          "<-": gd}],
        data_seed=41, prng_seed=42)
    wf.initialize(device=XLADevice())
    wf.run()
    assert wf.decision.min_validation_n_err_pt <= 25.0


# ----------------------------------------------------------------------
# the pre-norm residual block: rope, qk_norm, pre_norm, residual (PR 25)
# ----------------------------------------------------------------------
BLOCK_OPTIONS = {
    "rope": {"rope": {"theta": 10000}},
    "qk_norm": {"qk_norm": "rms"},
    "pre_norm": {"pre_norm": "rms"},
    "residual": {"residual": True},
    "all": {"rope": {"theta": 10000}, "qk_norm": "rms",
            "pre_norm": "rms", "residual": True, "include_bias": False},
}
BLOCK_D, BLOCK_H = 16, 2          # an even head size for the rotation
GAINS = ("gain_norm", "gain_q", "gain_k")


def build_block(device, x, options, params=None):
    """An attention unit with its GD pair; ``params`` (attr → array)
    overrides the drawn parameters, gains included."""
    prng.seed_all(5)
    wf = DummyWorkflow()
    src = DummyUnit(wf, output=Vector(np.asarray(x), name="x"))
    fwd = attention.MultiHeadAttention(wf, n_heads=BLOCK_H, causal=True,
                                       **options)
    fwd.link_attrs(src, ("input", "output"))
    fwd.initialize(device=device)
    rng = np.random.default_rng(11)
    for attr in GAINS:            # a gain of ones would hide its path
        vec = getattr(fwd, attr)
        if vec:
            vec.reset(rng.uniform(0.5, 1.5, vec.shape).astype(
                np.float32))
            vec.initialize(device)
    for attr, arr in (params or {}).items():
        vec = getattr(fwd, attr)
        vec.reset(np.array(arr, np.float32))
        vec.initialize(device)
    gd_u = attention.GDMultiHeadAttention(
        wf, learning_rate=0.05, gradient_moment=0.9)
    gd_u.forward_unit = fwd
    gd_u.link_attrs(fwd, "input", "output", "weights", "bias")
    gd_u.err_output = Vector(np.zeros(x.shape, np.float32), name="err")
    gd_u.initialize(device=device)
    return fwd, gd_u


def block_params(fwd) -> dict:
    out = {}
    for attr in fwd.EXPORT_PARAMS:
        vec = getattr(fwd, attr)
        if vec:
            vec.map_read()
            out[attr] = np.array(vec.mem, np.float32)
    return out


@pytest.mark.parametrize("name", sorted(BLOCK_OPTIONS))
def test_block_options_oracle_vs_vjp(name):
    """Each option alone and all together: the numpy oracle (analytic
    backward) and the XLA path (jax.vjp) agree on the output, on
    err_input and on every parameter after two momentum steps."""
    options = BLOCK_OPTIONS[name]
    rng = np.random.default_rng(7)
    x = rng.normal(0, 0.7, (B, T, BLOCK_D)).astype(np.float32)
    err = rng.normal(0, 0.1, (B, T, BLOCK_D)).astype(np.float32)
    np_f, np_g = build_block(NumpyDevice(), x, options)
    xla_f, xla_g = build_block(XLADevice(), x, options,
                               params=block_params(np_f))
    results = []
    for device, fwd, gd_u in ((np_f.device, np_f, np_g),
                              (xla_f.device, xla_f, xla_g)):
        for _ in range(2):
            fwd.run()
            gd_u.err_output.reset(err.copy())
            gd_u.err_output.initialize(device)
            gd_u.run()
        fwd.output.map_read()
        gd_u.err_input.map_read()
        results.append({**block_params(fwd),
                        "output": np.array(fwd.output.mem, np.float32),
                        "err_input": np.array(gd_u.err_input.mem,
                                              np.float32)})
    expected_gains = {"rope": (), "residual": (),
                      "qk_norm": ("gain_q", "gain_k"),
                      "pre_norm": ("gain_norm",), "all": GAINS}[name]
    assert {a for a in GAINS if a in results[0]} == set(expected_gains)
    for key, want in results[0].items():
        np.testing.assert_allclose(results[1][key], want, rtol=2e-3,
                                   atol=2e-5, err_msg=key)


def test_block_backward_matches_finite_differences():
    """The analytic oracle of the whole block (all four options)
    against central differences of Σ y·c: the input gradient and the
    three gains."""
    options = BLOCK_OPTIONS["all"]
    rng = np.random.default_rng(8)
    x = rng.normal(0, 0.7, (1, 4, BLOCK_D)).astype(np.float32)
    c = rng.normal(0, 1, x.shape).astype(np.float32)
    fwd, gd_u = build_block(NumpyDevice(), x, options)
    gains0 = {a: getattr(fwd, a).mem.copy() for a in GAINS}

    def loss(x_) -> float:
        y, _ = fwd._forward_np(np.asarray(x_, np.float32))
        return float((y.astype(np.float64) * c).sum())

    fwd.run()
    gd_u.err_output.reset(c.copy())
    gd_u.learning_rate, gd_u.gradient_moment = 1.0, 0.0  # W −= grad
    gd_u.run()
    analytic_dx = gd_u.err_input.mem.copy()
    grads = {a: gains0[a] - getattr(fwd, a).mem for a in GAINS}
    # the step moved every parameter: rebuild for the differences
    fwd, _ = build_block(NumpyDevice(), x, options)
    eps = 2e-3
    for idx in list(np.ndindex(*x.shape))[::5]:
        hi, lo = x.copy(), x.copy()
        hi[idx] += eps
        lo[idx] -= eps
        fd = (loss(hi) - loss(lo)) / (2 * eps)
        np.testing.assert_allclose(analytic_dx[idx], fd, rtol=2e-2,
                                   atol=2e-3)
    for attr in GAINS:
        vec = getattr(fwd, attr)
        for i in (0, 5, BLOCK_D - 1):
            keep = float(vec.mem[i])
            vec.mem[i] = keep + eps
            hi = loss(x)
            vec.mem[i] = keep - eps
            lo = loss(x)
            vec.mem[i] = keep
            np.testing.assert_allclose(grads[attr][i],
                                       (hi - lo) / (2 * eps),
                                       rtol=2e-2, atol=2e-3,
                                       err_msg=f"{attr}[{i}]")


def test_rope_is_a_rotation_by_position():
    """Half-split convention: position 0 is left alone, norms are kept,
    and the q·k score depends on the positions' difference only."""
    rng = np.random.default_rng(9)
    t, dh = 6, 8
    cos, sin = attention.rope_tables(np, t, dh, 10000.0)
    q = rng.normal(size=(1, t, 1, dh)).astype(np.float32)
    rot = attention.apply_rope(np, q, cos, sin)
    np.testing.assert_allclose(rot[:, 0], q[:, 0], atol=1e-7)
    np.testing.assert_allclose(np.linalg.norm(rot, axis=-1),
                               np.linalg.norm(q, axis=-1), rtol=1e-5)
    back = attention.apply_rope(np, rot, cos, sin, inverse=True)
    np.testing.assert_allclose(back, q, atol=1e-6)
    # the same two vectors at positions (1, 3) and (2, 4): equal score
    a, b_ = rng.normal(size=dh), rng.normal(size=dh)
    same = np.broadcast_to(a, (1, t, 1, dh)).astype(np.float32)
    other = np.broadcast_to(b_, (1, t, 1, dh)).astype(np.float32)
    ra = attention.apply_rope(np, same, cos, sin)[0, :, 0]
    rb = attention.apply_rope(np, other, cos, sin)[0, :, 0]
    np.testing.assert_allclose(ra[3] @ rb[1], ra[4] @ rb[2], rtol=1e-4)
    # x1' = x1 cos − x2 sin with x1, x2 the two HALVES of the head
    half = dh // 2
    want = q[0, 2, 0, :half] * cos[2] - q[0, 2, 0, half:] * sin[2]
    np.testing.assert_allclose(rot[0, 2, 0, :half], want, rtol=1e-5)


@pytest.mark.parametrize("t", [16, 12])
@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_rope_over_rows_is_the_same_rotation(backend, t):
    """``apply_rope_rows`` — the rotation on the (B, T/8, H, 8, dh) view
    of (B, T, D) rows, the one the flash path runs — equals
    ``apply_rope`` on (B, T, H, dh), whether or not 8 rows divide T."""
    import jax.numpy as jnp
    xp = np if backend == "numpy" else jnp
    b, h, dh = 2, 3, 8
    x = np.random.default_rng(4).normal(size=(b, t, h, dh)) \
        .astype(np.float32)
    want = attention.apply_rope(
        np, x, *attention.rope_tables(np, t, dh, 10000.0))
    got = attention.apply_rope_rows(
        xp, xp.asarray(x.reshape(b, t, h * dh)),
        *attention.rope_tables(xp, t, dh, 10000.0), h)
    np.testing.assert_allclose(np.asarray(got).reshape(x.shape), want,
                               atol=1e-6)


@pytest.mark.parametrize("name,d", [("bare", 128), ("all", 256)])
def test_block_through_the_flash_kernels_matches_the_oracle(name, d):
    """The unit's call site of the boundary-layout kernels (interpret
    mode): the bare layer hands them ONE (B, T, 3D) projection result
    (dh 64: pairs of heads), the pre-norm block three (B, T, D) tensors
    normed and rotated in the rows' layout (dh 128) — output, err_input
    and every parameter after two momentum steps agree with the numpy
    oracle's analytic backward."""
    from znicz_tpu.utils.config import root
    root.common.engine.flash_attention = True
    root.common.engine.pallas_interpret = True
    options = {} if name == "bare" else BLOCK_OPTIONS["all"]
    rng = np.random.default_rng(7)
    x = rng.normal(0, 0.7, (B, 16, d)).astype(np.float32)
    err = rng.normal(0, 0.1, x.shape).astype(np.float32)
    np_f, np_g = build_block(NumpyDevice(), x, options)
    xla_f, xla_g = build_block(XLADevice(), x, options,
                               params=block_params(np_f))
    assert xla_f._flash.runs
    assert (xla_f._flash.layout, xla_f._flash.head_pack) \
        == ("boundary", 128 // (d // BLOCK_H))
    results = []
    for device, fwd, gd_u in ((np_f.device, np_f, np_g),
                              (xla_f.device, xla_f, xla_g)):
        for _ in range(2):
            fwd.run()
            gd_u.err_output.reset(err.copy())
            gd_u.err_output.initialize(device)
            gd_u.run()
        fwd.output.map_read()
        gd_u.err_input.map_read()
        results.append({**block_params(fwd),
                        "output": np.array(fwd.output.mem, np.float32),
                        "err_input": np.array(gd_u.err_input.mem,
                                              np.float32)})
    for key, want in results[0].items():
        np.testing.assert_allclose(results[1][key], want, rtol=2e-3,
                                   atol=2e-5, err_msg=key)


@pytest.mark.parametrize("precision", ["float32", "bfloat16"])
def test_default_options_leave_the_bare_layer_bit_identical(precision):
    """With every block option off (``attn_lm_base``'s layer) the XLA
    forward is, bit for bit, the bare layer's formula as it stood
    before the options came: projection, cast, split, core, output
    projection — and the unit allocates no gain."""
    import jax.numpy as jnp
    from znicz_tpu.parallel.ring_attention import local_attention
    from znicz_tpu.utils.config import root
    root.common.precision_type = precision
    x = _rand(12)
    fwd = build(XLADevice(), x, causal=True)
    assert not (fwd.gain_norm or fwd.gain_q or fwd.gain_k)
    assert not (fwd.pre_norm or fwd.qk_norm or fwd.residual) \
        and fwd.rope_theta is None
    fwd.run()
    fwd.output.map_read()
    dt = fwd.mxu_dtype
    assert (dt is not None) == (precision == "bfloat16")
    qkv = fwd.mxu_dot(jnp, jnp.asarray(x).reshape(B * T, D),
                      fwd.weights.devmem) + fwd.bias.devmem
    if dt is not None:
        qkv = qkv.astype(dt)
    q, k, v = attention._split_heads(qkv.reshape(B, T, 3 * D), H)
    o = local_attention(q, k, v, causal=True, dot_dtype=dt)
    y = fwd.mxu_dot(jnp, o.reshape(B * T, D), fwd.weights_out.devmem) \
        + fwd.bias_out.devmem
    stored = y.reshape(B, T, D).astype(fwd.output.dtype)  # bf16 mode
    np.testing.assert_array_equal(                        # stores bf16
        np.asarray(fwd.output.mem, np.float32),
        np.asarray(stored, np.float32))
