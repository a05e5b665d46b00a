"""Xing4.0-29B-A4B's mechanisms (PR 46) at small widths, on the CPU,
seeded: Sinkhorn's iteration and the clamp, the n = 1 residual path
against the ``residual: true`` block, the skip edge's cotangent, the
interleaved against the half-split rotation under YaRN's blended
frequencies, the query latent and the score scale, a table that does
not pair its READs and WRITEs, the share test (the eight shares' routed
parts plus the shared expert once add up to the uncut layer), and the
toy ``StandardWorkflow`` (``znbench/tests/data/toy``: hidden 64, 4
streams, 2 heads of 128 + 64 / 128, latents 48 and 32 + 64, 16 experts
with 2 held, T 64) against the benchmark's plain reference
(``znbench/reference/xing.py``): every table entry's output, the loss,
EVERY parameter gradient, on both backends; each control differs from
the f32 system by far more than the plain reference does; export,
serving and a looped span refuse the table by name."""

import copy
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import xing_controls as controls
from znicz_tpu.backends import NumpyDevice, XLADevice
from znicz_tpu.loader.base import TRAIN
from znicz_tpu.loader.fullbatch import ArrayLoader
from znicz_tpu.memory import Vector
from znicz_tpu.models.standard_workflow import StandardWorkflow
from znicz_tpu.observe import metrics as obs_metrics
from znicz_tpu.ops import attention, moe, streams
from znicz_tpu.utils import prng
from znicz_tpu.utils.config import reset_root, root
from znicz_tpu.workflow import Workflow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ, BATCH = 64, 2


def reference():
    path = os.path.join(REPO, "znbench", "reference", "xing.py")
    spec = importlib.util.spec_from_file_location("ref_xing", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def config(toy: bool = True) -> dict:
    parts = ("tests", "data", "toy") if toy else ()
    with open(os.path.join(REPO, "znbench", *parts, "configs",
                           "xing4_0_29b_a4b.json")) as fh:
        return json.load(fh)


def rel(got, want) -> float:
    want = np.asarray(want, np.float32)
    return float(np.abs(np.asarray(got, np.float32).reshape(want.shape)
                        - want).max() / (np.abs(want).max() + 1e-30))


# ----------------------------------------------------------------------
# Sinkhorn's iteration and the clamp
# ----------------------------------------------------------------------
@pytest.mark.parametrize("xp", [np, jnp], ids=["numpy", "jax"])
def test_twenty_iterations_reach_the_doubly_stochastic_matrices(xp):
    """From clamped random logits (uniform within ± 2, a ratio of up
    to e⁴ between a matrix's entries) rows and columns sum to 1 within
    1e-4 after 20 iterations; after ONE the rows are still off by far
    more.  (Within ± 3 the worst of 192 matrices is still 4e-3 off
    after 20: the iteration's rate falls with the ratio, which is what
    ``znicz_stream_maps`` watches.)"""
    rng = np.random.default_rng(3)
    logits = np.clip(rng.uniform(-2, 2, (2, 4, 4, 96)), -30, 30)
    start = xp.asarray(np.exp(logits), xp.float32)
    done = np.asarray(streams.sinkhorn(xp, start, 20, 1e-6))
    assert np.abs(done.sum(axis=2) - 1).max() < 1e-4      # rows
    assert np.abs(done.sum(axis=1) - 1).max() < 1e-4      # columns
    assert (done > 0).all()
    once = np.asarray(streams.sinkhorn(xp, start, 1, 1e-6))
    assert np.abs(once.sum(axis=2) - 1).max() > 1e-2
    # the reference's loop, matrices last, gives the same matrices
    want = reference().sinkhorn(
        jnp.asarray(np.moveaxis(np.exp(logits), -1, 1), jnp.float32),
        20, 1e-6)
    assert rel(np.moveaxis(done, -1, 1), want) < 1e-5


def _read_unit(n: int, d: int, t: int = 8, **options):
    reset_root()
    prng.seed_all(11)
    unit = streams.StreamRead(Workflow(name="read"), n_streams=n,
                              **options)
    x = np.random.default_rng(12).normal(0, 1, (BATCH, n * d, t))
    unit.input = Vector(x.astype(np.float32))
    unit.initialize(device=XLADevice())
    return unit


def test_the_clamp_s_gradient_is_zero_outside_its_bounds():
    """b_res = ± 40 on two entries: the clamp holds them at ± 30 (the
    counter sees both), and neither receives a gradient; an entry
    inside the bounds does."""
    unit = _read_unit(4, 16)
    bias = np.array(unit.maps_bias.mem)
    bias[8 + 1], bias[8 + 6] = 40.0, -40.0         # b_res[0, 1], [1, 2]

    def total(b):
        (h, h_post, h_res), stats = unit.xla_forward(
            unit.input.devmem, unit.weights.devmem, b,
            unit.maps_alpha.devmem)
        return (h_res * jnp.arange(16.0).reshape(1, 4, 4, 1)).sum(), stats

    grad, stats = jax.grad(total, has_aux=True)(jnp.asarray(bias))
    grad = np.asarray(grad)[8:].reshape(4, 4)
    assert grad[0, 1] == 0 and grad[1, 2] == 0
    assert np.abs(grad[2, 2]) > 1e-4
    assert float(stats[2]) == 2 * BATCH * 8        # entries clamped


# ----------------------------------------------------------------------
# n = 1 with the maps forced to 1 is the residual block
# ----------------------------------------------------------------------
def _one_stream_table(width: int) -> list:
    gd = {"learning_rate": 0.5, "gradient_moment": 0.0}
    mlp = {"width": width, "pre_norm": "rms", "norm_eps": 1e-6}
    head = [{"type": "embedding", "->": {"vocab_size": 31, "dim": 32},
             "<-": gd}]
    tail = [{"type": "softmax", "<-": gd, "->": {
        "output_sample_shape": 31, "per_position": True,
        "include_bias": False}}]
    block = [{"type": "gated_mlp", "->": dict(mlp, residual=True),
              "<-": gd}]
    hyper = [{"type": "stream_open", "->": {"n_streams": 1}, "<-": gd},
             {"type": "stream_read", "->": {
                 "n_streams": 1, "alpha_init": 0.0, "sinkhorn_eps": 0.0},
              "<-": gd},
             {"type": "gated_mlp", "->": dict(mlp, residual=False),
              "<-": gd},
             {"type": "stream_write", "->": {"n_streams": 1}, "<-": gd},
             {"type": "stream_close", "->": {"n_streams": 1}, "<-": gd}]
    return head + block + tail, head + hyper + tail


def _small(table, name):
    reset_root()
    ids = np.random.default_rng(4).integers(0, 31, (BATCH, 17))
    prng.seed_all(21)
    wf = StandardWorkflow(
        name=name, loader_factory=lambda w: ArrayLoader(
            w, train_data=ids[:, :-1].astype(np.float32),
            train_labels=ids[:, 1:].astype(np.int32),
            minibatch_size=BATCH, shuffle_limit=0),
        layers=table, decision_config={"max_epochs": 1})
    wf.initialize(device=XLADevice())
    return wf


def test_one_stream_with_its_maps_at_one_is_the_residual_block():
    """H_pre = H_post = H_res = 1 (b_pre = 40: σ = 1 in f32; b_post = 0:
    2 σ(0) = 1; a 1 × 1 M over itself, ε = 0; α = 0): open → READ →
    MLP → WRITE → close is x + F(norm(x)), the existing ``residual:
    true`` unit on the same weights — its output, and after one step
    its weights."""
    plain_table, hyper_table = _one_stream_table(48)
    plain, hyper = _small(plain_table, "plain"), _small(hyper_table, "hc")
    read = hyper.forwards[2]
    read.maps_bias.map_write()
    read.maps_bias.mem[...] = (40.0, 0.0, 30.0)
    for index, other in ((0, 0), (1, 3), (2, 6)):
        for attr in plain.forwards[index].EXPORT_PARAMS:
            vec = getattr(plain.forwards[index], attr)
            if vec:
                vec.map_read()
                mine = getattr(hyper.forwards[other], attr)
                mine.map_write()
                mine.mem[...] = vec.mem
    plain.run()
    hyper.run()
    for vec in (plain.forwards[1].output, hyper.forwards[5].output,
                read.h_post, read.h_res):
        vec.map_read()
    assert (read.h_post.mem == 1).all() and (read.h_res.mem == 1).all()
    assert rel(hyper.forwards[5].output.mem,
               plain.forwards[1].output.mem) < 1e-6
    for attr in ("weights", "weights_up", "weights_down", "gain_norm"):
        a, b = getattr(plain.forwards[1], attr), \
            getattr(hyper.forwards[3], attr)
        a.map_read(), b.map_read()
        assert rel(b.mem, a.mem) < 1e-5, attr
    assert plain.decision.epoch_loss[TRAIN] == pytest.approx(
        hyper.decision.epoch_loss[TRAIN], rel=1e-6)


# ----------------------------------------------------------------------
# the skip edge's cotangent
# ----------------------------------------------------------------------
class _Handed:
    """A stand-in for the stream GD after a WRITE's."""
    STREAM_OUT = True

    def __init__(self, err):
        self.err_stream = err


@pytest.mark.parametrize("backend", ["xla", "numpy"])
def test_a_read_s_input_gradient_sums_both_ways_back(backend):
    """L = Σ G · X′ with X′ = WRITE(f = h, X, maps(X)), h = READ(X): the
    READ's GD hands on dL/dX = what returns through h and the maps +
    what returns through its WRITE's skip edge (H_resᵀ G) — each part
    against the reference's, and neither is nil."""
    n, d, t = 4, 16, 8
    device = XLADevice() if backend == "xla" else NumpyDevice()
    reset_root()
    prng.seed_all(13)
    wf = Workflow(name="edge")
    rng = np.random.default_rng(14)
    x = rng.normal(0, 1, (BATCH, n * d, t)).astype(np.float32)
    g = rng.normal(0, 1, (BATCH, n * d, t)).astype(np.float32)
    read = streams.StreamRead(wf, n_streams=n, alpha_init=1.0)
    read.input = Vector(x.copy())
    write = streams.StreamWrite(wf, n_streams=n)
    write.read_unit, read.write_unit = read, write
    write.link_attrs(read, ("input", "output"))
    gd_write = streams.GDStreamWrite(wf)
    gd_read = streams.GDStreamRead(wf, learning_rate=0.0)
    for gd, fwd in ((gd_write, write), (gd_read, read)):
        gd.forward_unit = fwd
        gd.link_attrs(fwd, "input", "output", "weights", "bias")
    gd_write.stream_gd = _Handed(g if backend == "numpy"
                                 else jnp.asarray(g))
    gd_read.link_attrs(gd_write, ("err_output", "err_input"))
    for unit in (read, write, gd_write, gd_read):
        unit.initialize(device=device)
    for unit in (read, write, gd_write):
        unit.run()
    skip = np.asarray(read._skip[0])
    gd_read.run()
    total = np.asarray(gd_read.err_stream)

    ref = reference()
    for vec in (read.weights, read.maps_bias, read.maps_alpha):
        vec.map_read()
    params = {"layer0_weights": read.weights.mem,
              "layer0_maps_bias": read.maps_bias.mem,
              "layer0_maps_alpha": read.maps_alpha.mem}
    spec = {"n_streams": n}

    def loss(x_maps, x_skip):
        """x as (B, T, n·D); the WRITE's own use of X apart."""
        h_pre, h_post, h_res = ref.stream_maps(x_maps, params, 0, spec)
        h = jnp.einsum("btj,btjd->btd", h_pre, ref.streams_of(x_maps, n))
        out = jnp.einsum("btij,btjd->btid", h_res,
                         ref.streams_of(x_skip, n)) \
            + h_post[..., None] * h[:, :, None, :]
        return (out.reshape(x_maps.shape)
                * jnp.swapaxes(jnp.asarray(g), 1, 2)).sum()

    rows = jnp.swapaxes(jnp.asarray(x), 1, 2)
    with jax.default_matmul_precision("highest"):
        through, edge = (np.swapaxes(np.asarray(part), 1, 2)
                         for part in jax.grad(loss, (0, 1))(rows, rows))
    assert np.abs(through).max() > 0.1 and np.abs(edge).max() > 0.1
    assert rel(skip, edge) < 1e-5
    assert rel(total, through + edge) < 1e-5


# ----------------------------------------------------------------------
# the rotation, the query latent, the score scale
# ----------------------------------------------------------------------
YARN = {"factor": 64, "original_max_position_embeddings": 16,
        "beta_fast": 32, "beta_slow": 1, "attention_factor": 1.0}


def test_interleaved_and_half_split_rotation_give_the_same_scores():
    """Under YaRN's blended frequencies: the unit rotates the two
    halves of the 64 rotary dims, the reference the published pairs
    (2i, 2i + 1) on the columns in the order ``pairs``; q · k is the
    same number, cos and sin are not scaled, and the blend is not the
    plain rotation."""
    ref = reference()
    rng = np.random.default_rng(6)
    q = rng.normal(0, 1, (1, SEQ, 2, 64)).astype(np.float32)
    k = rng.normal(0, 1, (1, SEQ, 1, 64)).astype(np.float32)
    cos, sin = attention.rope_tables(np, SEQ, 64, 1e4, YARN)
    assert cos.max() == 1.0                 # attention_factor 1
    ours = np.einsum("bqhd,bkd->bhqk", attention.apply_rope(np, q, cos,
                                                            sin),
                     attention.apply_rope(np, k, cos, sin)[:, :, 0])
    order = ref.pairs(64)
    theirs = np.einsum(
        "bqhd,bkd->bhqk",
        np.asarray(ref.rope_interleaved(jnp.asarray(q[..., order]), 1e4,
                                        YARN)),
        np.asarray(ref.rope_interleaved(jnp.asarray(k[..., order]), 1e4,
                                        YARN))[:, :, 0])
    assert rel(ours, theirs) < 1e-5
    plain = attention.rope_tables(np, SEQ, 64, 1e4)[0]
    assert np.abs(plain - cos).max() > 0.5
    np.testing.assert_allclose(
        ref.yarn_inv_freq(64, 1e4, YARN),
        attention.yarn_inv_freq(64, 1e4, YARN), rtol=1e-12)


def _latent(device, x, **options):
    reset_root()
    prng.seed_all(9)
    spec = dict(n_heads=2, causal=True, include_bias=False,
                pre_norm="rms", kv_latent=32, qk_nope=128, qk_rope=64,
                v_head_dim=128, rope={"theta": 1e4, "yarn": YARN},
                norm_eps=1e-6, **options)
    unit = attention.MultiHeadAttention(Workflow(name="mla"), **spec)
    unit.input = Vector(x.copy())
    unit.initialize(device=device)
    rng = np.random.default_rng(10)
    for gain in (unit.gain_norm, unit.gain_latent, unit.gain_q_latent):
        if gain:
            gain.map_invalidate()
            gain.mem[...] = rng.uniform(0.5, 1.5, gain.shape)
            gain.unmap()
    unit.run()
    unit.output.map_read()
    params = {}
    for attr in unit.EXPORT_PARAMS:
        vec = getattr(unit, attr)
        if vec:
            vec.map_read()
            params[f"layer0_{attr}"] = np.array(vec.mem)
    return unit, params, spec


@pytest.mark.parametrize("backend", ["xla", "numpy"])
@pytest.mark.parametrize("scale", [None, 0.5], ids=["plain", "given"])
@pytest.mark.parametrize("q_latent", [None, 48], ids=["fused", "q_latent"])
def test_the_query_latent_and_the_score_scale(backend, scale, q_latent):
    """With and without each: the unit's output is the reference's; the
    query latent has its own down-projection columns, up-projection
    and gain; the given scale replaces 192^-1/2."""
    x = np.random.default_rng(8).normal(
        0, 1, (BATCH, SEQ, 64)).astype(np.float32)
    device = XLADevice() if backend == "xla" else NumpyDevice()
    options = {k: v for k, v in (("score_scale", scale),
                                 ("q_latent", q_latent)) if v is not None}
    unit, params, spec = _latent(device, x, **options)
    if q_latent:
        assert unit.weights.shape == (64, 48 + 32 + 64)
        assert unit.weights_q_up.shape == (48, 2 * 192)
        assert unit.gain_q_latent.shape == (48,)
    else:
        assert unit.weights.shape == (64, 2 * 192 + 32 + 64)
        assert not unit.weights_q_up and not unit.gain_q_latent
    ref = reference()
    with jax.default_matmul_precision("highest"):
        m = ref.rms_norm(jnp.asarray(x), params["layer0_gain_norm"], 1e-6)
        want = ref.latent_mixer(m, params, 0, spec)
        other = ref.latent_mixer(m, params, 0, dict(
            spec, score_scale=0.25))
    assert rel(unit.output.mem, want) < 1e-5
    assert rel(other, want) > 1e-2          # the scale decides something


def test_the_two_options_need_a_latent_layer():
    with pytest.raises(ValueError, match="kv_latent"):
        attention.MultiHeadAttention(Workflow(name="w"), n_heads=2,
                                     q_latent=8)
    with pytest.raises(ValueError, match="kv_latent"):
        attention.MultiHeadAttention(Workflow(name="w"), n_heads=2,
                                     score_scale=0.1)


# ----------------------------------------------------------------------
# a table that does not pair its READs and WRITEs
# ----------------------------------------------------------------------
def _typed(*kinds) -> list:
    gd = {"learning_rate": 0.1}
    made = {"embedding": {"vocab_size": 31, "dim": 32},
            "gated_mlp": {"width": 48},
            "softmax": {"output_sample_shape": 31, "per_position": True,
                        "include_bias": False}}
    return [{"type": kind, "->": dict(made.get(kind, {"n_streams": 2})),
             "<-": gd} for kind in kinds]


@pytest.mark.parametrize("kinds,index,word", [
    (("embedding", "stream_open", "gated_mlp", "stream_write",
      "stream_close", "softmax"), 3, "no stream_read"),
    (("embedding", "stream_open", "stream_read", "gated_mlp",
      "stream_close", "softmax"), 4, "reads the streams"),
    (("embedding", "stream_open", "stream_read", "stream_write",
      "stream_close", "softmax"), 3, "no sublayer"),
    (("embedding", "stream_open", "stream_read", "gated_mlp",
      "stream_write", "gated_mlp", "stream_write", "stream_close",
      "softmax"), 6, "layer 4, a stream_write"),
    (("embedding", "stream_read", "gated_mlp", "stream_write",
      "softmax"), 1, "reads the streams"),
    (("embedding", "stream_open", "stream_read", "gated_mlp",
      "stream_write", "softmax"), 4, "hands the streams on"),
], ids=["write_without_read", "read_never_written", "nothing_between",
        "two_writes", "never_opened", "never_closed"])
def test_a_table_that_does_not_pair_them_is_refused_by_index(
        kinds, index, word):
    reset_root()
    with pytest.raises(ValueError) as said:
        _small(_typed(*kinds), "unpaired")
    assert f"layer {index}" in str(said.value), str(said.value)
    assert word in str(said.value)


def test_a_looped_span_refuses_a_stream_unit_by_name():
    table = _typed("embedding", "stream_open", "stream_read", "gated_mlp",
                   "stream_write", "stream_close", "softmax")
    for layer in table[2:5]:
        layer["passes"] = 2
    with pytest.raises((NotImplementedError, ValueError)) as said:
        _small(table, "looped")
    assert "Stream" in str(said.value)


# ----------------------------------------------------------------------
# the share test
# ----------------------------------------------------------------------
SPEC = {"n_experts": 16, "top_k": 3, "width": 32, "norm_topk": True,
        "score": "sigmoid", "routed_scale": 2.0, "shared_width": 32,
        "select_bias": True, "pre_norm": "rms", "residual": False,
        "norm_eps": 1e-6}


def _share(full, x, bias, held):
    reset_root()
    unit = moe.MoE(Workflow(name="share"), held=held, **SPEC)
    unit.input = Vector(x.copy())
    for attr in unit.EXPORT_PARAMS:
        value = full[f"layer0_{attr}"]
        if attr in ("weights_gate", "weights_up", "weights_down"):
            value = value[list(held)]        # this chip's slabs
        getattr(unit, attr).reset(value.copy())
    unit.initialize(device=XLADevice())
    unit.select_bias.map_write()
    unit.select_bias.mem[...] = bias
    unit.select_bias.unmap()
    unit.run()
    unit.output.map_read()
    unit.last_choice.map_read()
    return unit


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """What each of the 8 chips adds for its 2 of 16 experts, with the
    shared expert (what every chip computes alike) counted once, is the
    uncut reference's output of the whole layer — under a bias that
    moves the choice."""
    rng = np.random.default_rng(5)
    d, experts, width = 64, SPEC["n_experts"], SPEC["width"]
    full = {"layer0_weights": rng.normal(0, 0.5, (d, experts)),
            "layer0_gain_norm": rng.uniform(0.7, 1.3, d)}
    for name, shape in (("gate", (experts, d, width)),
                        ("up", (experts, d, width)),
                        ("down", (experts, width, d)),
                        ("shared_gate", (d, width)),
                        ("shared_up", (d, width)),
                        ("shared_down", (width, d))):
        full[f"layer0_weights_{name}"] = rng.normal(0, 0.2, shape)
    full = {k: v.astype(np.float32) for k, v in full.items()}
    x = rng.normal(0, 1, (BATCH, SEQ, d)).astype(np.float32)
    bias = rng.uniform(-0.1, 0.1, experts).astype(np.float32)
    ref = reference()
    with jax.default_matmul_precision("highest"):
        m = ref._normed(jnp.asarray(x), full, 0, SPEC)
        uncut, _, chosen = ref.moe_block(m, full, 0, SPEC, bias=bias)
        alike = ref.moe_block(m, full, 0, SPEC, chosen, held=[])[0]
        unbiased = ref.moe_block(m, full, 0, SPEC)[2]
    assert (np.sort(chosen, -1) != np.sort(unbiased, -1)).any()
    assert np.abs(np.asarray(alike)).max() > 0.05    # the shared expert
    total = np.asarray(alike, np.float64)
    for share in range(8):
        held = (2 * share, 2 * share + 1)
        unit = _share(full, x, bias, held)
        np.testing.assert_array_equal(     # every chip routes over all 16
            np.sort(unit.last_choice.mem.reshape(-1, 3), axis=-1),
            np.sort(chosen, axis=-1))
        part = np.asarray(unit.output.mem, np.float64) - alike
        assert np.abs(part).max() > 0.01, share
        total += part
    np.testing.assert_allclose(total, np.asarray(uncut), atol=1e-4)


# ----------------------------------------------------------------------
# the toy model against the plain reference
# ----------------------------------------------------------------------
def layers(lr: float, moment: float) -> list:
    table = copy.deepcopy(config()["workflow"]["layers"])
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


def build(device, table, name="xing_ref", steps: int = 1):
    vocab = config()["input"]["vocab"]
    rng = np.random.default_rng(17)
    ids = rng.integers(0, vocab, (BATCH * steps, SEQ + 1))
    x, y = ids[:, :-1], ids[:, 1:]
    prng.seed_all(31)
    wf = StandardWorkflow(
        name=name,
        loader_factory=lambda w: ArrayLoader(
            w, train_data=x.astype(np.float32),
            train_labels=y.astype(np.int32), minibatch_size=BATCH,
            shuffle_limit=0),
        layers=table, decision_config={"max_epochs": 1})
    wf.initialize(device=device)
    rng = np.random.default_rng(18)
    for unit in wf.forwards:
        # gains and scalars of one would hide their path, a bias of
        # zero its own
        for attr in ("gain_norm", "gain_latent", "gain_q_latent",
                     "maps_alpha"):
            vec = getattr(unit, attr, None)
            if vec:
                vec.map_invalidate()
                vec.mem[...] = rng.uniform(0.7, 1.3, vec.shape)
        if getattr(unit, "select_bias_on", False):
            unit.select_bias.map_invalidate()
            unit.select_bias.mem[...] = rng.uniform(
                -0.05, 0.05, unit.select_bias.shape)
    return wf, x, y


@pytest.fixture(scope="module", params=["xla", "numpy"])
def one_step(request):
    """One plain-SGD step at lr 1 (W −= gradient) of the system in f32
    — on the XLA backend with every kernel interpreted, on the numpy
    backend through the units' oracles — with the parameters and the
    selection biases before it and the tokens it saw."""
    reset_root()
    engine = root.common.engine
    if request.param == "xla":
        engine.pallas_interpret = True
        engine.flash_attention = True
        engine.moe_grouped_matmul = True
    table = layers(1.0, 0.0)
    wf, x, y = build((XLADevice if request.param == "xla"
                      else NumpyDevice)(), table)
    before = params_of(wf)
    bias = {}
    for i, unit in enumerate(wf.forwards):
        if getattr(unit, "select_bias_on", False):
            unit.select_bias.map_read()
            bias[i] = np.array(unit.select_bias.mem)
    wf.run()
    reset_root()
    return wf, table, before, bias, x, y, request.param


SUBLAYER = ["stream_read", "latent_attention", "stream_write",
            "stream_read"]


def test_the_toy_model_is_the_cell_s_model_in_small(one_step):
    wf, table, *_, backend = one_step
    kinds = [layer["type"] for layer in table]
    assert kinds == ["embedding", "stream_open"] \
        + SUBLAYER + ["gated_mlp", "stream_write"] \
        + SUBLAYER + ["moe", "stream_write"] \
        + ["stream_close", "rms_norm", "softmax"]
    real = config(toy=False)["workflow"]["layers"]
    assert [layer["type"] for layer in real] == kinds[:8] \
        + (SUBLAYER + ["moe", "stream_write"]) * 4 + kinds[-3:]
    options = {layer["type"]: set(layer["->"]) for layer in real}
    for layer in table:                      # the same options, smaller
        assert set(layer["->"]) == options[layer["type"]]
    for i, unit in enumerate(wf.forwards):
        if isinstance(unit, streams.StreamRead):
            assert unit.weights.shape == (256, 24)
            assert unit.maps_bias.shape == (24,)
            assert unit.maps_alpha.shape == (3,)
            assert unit.write_unit is wf.forwards[i + 2]
            assert unit.write_unit.read_unit is unit
        if isinstance(unit, (streams.StreamOpen, streams.StreamWrite)):
            assert unit.output.shape == (BATCH, 256, SEQ)
        if isinstance(unit, attention.MultiHeadAttention):
            assert unit.q_latent == 48 and not unit.residual
            assert unit.score_scale == pytest.approx(
                192 ** -0.5 * (0.1 * np.log(64) + 1) ** 2)
            if backend == "xla":
                assert unit._flash.runs and unit._flash.interpret
        if isinstance(unit, moe.MoE):
            assert unit.select_bias_on and unit.groups is None
            assert unit.held == (0, 1) and unit.shared_width == 32
            assert not unit.residual and unit.aux_loss_weight == 0


def test_the_published_widths_and_the_cut_are_in_the_file():
    file = config(toy=False)
    assert file["reduced"] == ["num_hidden_layers", "n_routed_experts",
                               "vocab_size", "num_nextn_predict_layers"]
    assert file["published"] == {
        "num_hidden_layers": 40, "n_routed_experts": 64,
        "vocab_size": 131072, "num_nextn_predict_layers": 1}
    assert (file["hidden_size"], file["num_attention_heads"],
            file["q_lora_rank"], file["kv_lora_rank"],
            file["qk_nope_head_dim"], file["qk_rope_head_dim"],
            file["v_head_dim"], file["intermediate_size"],
            file["moe_intermediate_size"], file["num_experts_per_tok"],
            file["routed_scaling_factor"], file["hc_mult"],
            file["hc_sinkhorn_iters"], file["hc_eps"],
            file["mhc_h_res_clamp_min"], file["mhc_h_res_clamp_max"],
            file["rms_norm_eps"], file["first_k_dense_replace"]) \
        == (3584, 32, 768, 512, 128, 64, 128, 9216, 1024, 4, 2, 4, 20,
            1e-6, -30, 30, 1e-6, 2)
    assert file["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    assert (file["num_hidden_layers"], file["n_routed_experts"],
            file["vocab_size"], file["num_nextn_predict_layers"]) \
        == (5, 8, 16384, 0)
    table = file["workflow"]["layers"]
    assert len(table) == 2 + 10 * 3 + 3
    read, mla, write = (table[i]["->"] for i in (2, 3, 4))
    assert read == {"n_streams": 4, "sinkhorn_iters": 20,
                    "sinkhorn_eps": 1e-6, "clamp": 30.0, "norm_eps": 1e-6,
                    "alpha_init": 1.0}
    assert write == {"n_streams": 4}
    assert (mla["n_heads"], mla["q_latent"], mla["kv_latent"],
            mla["qk_nope"], mla["qk_rope"], mla["v_head_dim"],
            mla["residual"], mla["pre_norm"]) \
        == (32, 768, 512, 128, 64, 128, False, "rms")
    assert mla["score_scale"] == pytest.approx(
        192 ** -0.5 * (0.1 * np.log(64) + 1) ** 2, rel=1e-12)
    assert mla["rope"] == {"theta": 10000, "yarn": {
        "factor": 64, "original_max_position_embeddings": 4096,
        "beta_fast": 32, "beta_slow": 1, "attention_factor": 1.0}}
    assert table[6]["->"] == {"width": 9216, "pre_norm": "rms",
                              "residual": False, "norm_eps": 1e-6}
    experts = table[12]["->"]
    assert (experts["n_experts"], experts["top_k"], experts["width"],
            experts["shared_width"], experts["held"],
            experts["select_bias"], experts["score"],
            experts["routed_scale"], experts["norm_topk"],
            experts["residual"], experts["aux_loss_weight"]) \
        == (64, 4, 1024, 1024, list(range(8)), True, "sigmoid", 2.0,
            True, False, 0.0)
    assert "groups" not in experts
    assert set(file["reference_tolerance"]) == {
        "embedding", "layers", "router_logits", "router_gap"}
    for key in ("residual_path", "stream_ends", "maps_init", "mla",
                "rope", "weight_layout", "router", "aux_loss",
                "optimizer", "data", "init", "buffer"):
        assert file["assumed"][key]
    for word in ("8 expert-parallel", "8 chips", "8-stage"):
        assert word in file["deployment"]
    assert "759 M" in file["reduced_why"]["arithmetic"]


def test_layer_outputs_and_probabilities(one_step):
    """f32 on both sides, the program in tiles and position-minor
    streams, the reference in whole arrays, choosing its own experts:
    what is left is the order of summation."""
    wf, table, before, bias, x, y, _ = one_step
    outs, router = reference().run(before, table, x, bias=bias)
    assert len(outs) == len(wf.forwards) == len(table)
    for i, (unit, want) in enumerate(zip(wf.forwards, outs)):
        unit.output.map_read()
        assert unit.output.mem.shape == np.asarray(want).shape
        assert rel(unit.output.mem, want) < 1e-5, (i, table[i]["type"])
        if table[i]["type"] == "moe":      # the same experts, by itself
            unit.last_choice.map_read()
            np.testing.assert_array_equal(
                np.sort(unit.last_choice.mem.reshape(-1, 3), -1),
                np.sort(router["chosen"][i], -1))
    streams_out = np.asarray(outs[4])      # four streams that differ
    assert np.abs(streams_out[:, :64] - streams_out[:, 64:128]).max() > 0.1


def test_loss_and_every_gradient(one_step):
    """The step ran plain SGD at lr 1, so parameter − parameter after
    IS the system's gradient of the loss: compared with the reference's
    ``value_and_grad`` for every tensor — the maps' φ, b and α, both
    latents' gains, both up-projections, W_r, the experts among them."""
    wf, table, before, bias, x, y, _ = one_step
    value, grads = reference().loss_and_grads(before, table, x, y,
                                              bias=bias)
    after = params_of(wf)
    # embedding, 4 × 3 (maps), 2 × 7 (mixer), 4 (MLP), 8 (expert
    # layer), final gain, head
    assert set(grads) == set(before)
    assert len(before) == 1 + 4 * 3 + 2 * 7 + 4 + 8 + 2
    for name in ("layer2_weights", "layer2_maps_bias", "layer2_maps_alpha",
                 "layer3_weights_q_up", "layer3_gain_q_latent",
                 "layer3_gain_latent", "layer3_weights_kv_up",
                 "layer12_weights", "layer12_weights_shared_up"):
        assert name in grads
    for name, want in grads.items():
        got = before[name] - after[name]
        scale = np.abs(want).max()
        assert scale > 0, name
        # b's gradient is a sum over every token of terms of both
        # signs: f32 keeps it to a few 1e-5 of its size
        limit = 3e-4 if name.endswith("maps_bias") else 1e-4
        assert np.abs(got - want).max() <= limit * scale, (
            name, np.abs(got - want).max() / scale)
    assert wf.decision.epoch_loss[TRAIN] == pytest.approx(value, rel=1e-4)
    # the bias moved by its rule, and is no parameter of the loss
    for i, unit in enumerate(wf.forwards):
        if getattr(unit, "select_bias_on", False):
            for vec in (unit.select_bias, unit.select_load):
                vec.map_read()
            load = unit.select_load.mem
            np.testing.assert_allclose(
                unit.select_bias.mem,
                bias[i] + 1e-3 * np.sign(load.mean() - load), atol=1e-8)


def test_what_sinkhorn_reached_is_read_once_an_epoch(one_step):
    wf, table, *_ = one_step
    opened = wf.forwards[1]
    seen = {stat: obs_metrics.stream_maps(opened.name, stat).value
            for stat in ("row_gap", "col_gap", "clamped", "sublayers",
                         "streams")}
    assert seen["sublayers"] == 4 and seen["streams"] == 4
    assert seen["clamped"] == 0
    assert 0 < seen["col_gap"] < 1e-5 < seen["row_gap"] < 0.1
    opened.stream_stats.map_read()
    assert not opened.stream_stats.mem.any()         # started over


CONTROLS = ["float8", "h_post_without_its_2", "no_stream_norm",
            "no_query_latent_norm", "score_scale_without_yarn",
            "no_routed_scaling", "one_sinkhorn_iteration", "rows_only"]


@pytest.mark.parametrize("what", CONTROLS)
def test_a_reference_made_wrong_is_far_from_the_f32_system(one_step,
                                                            what):
    """Each control of ``benchmarks/xing_controls.py`` — the two
    READINGS the cell's one limit does not separate among them — moves
    a layer's output by more than 1e-2 of its range where the plain
    reference agrees with the f32 system to 1e-5: a thousand times
    apart, whatever the limit at the cell's sizes."""
    wf, table, before, bias, x, y, _ = one_step
    ref = reference()
    listed = {name: how for name, *how in controls.controls(ref, table)
              + controls.readings(ref, table)}
    assert set(CONTROLS) == set(listed)
    routing = {}
    for i in bias:                  # the system's own choice, as the
        if i <= listed[what][0]:    # driver hands it to the reference
            wf.forwards[i].last_choice.map_read()
            routing[i] = np.asarray(wf.forwards[i].last_choice.mem) \
                .reshape(-1, 3).astype(np.int64)
    outs = controls.spoiled(ref, *listed[what]).forward(
        before, table, x, routing=routing, bias=bias)
    worst = 0.0
    for unit, want in zip(wf.forwards[1:], outs[1:]):
        unit.output.map_read()
        worst = max(worst, rel(unit.output.mem, want))
    assert worst > 1e-2, (what, worst)


# ----------------------------------------------------------------------
# the other driver, export and serving: correct, or refusing by name
# ----------------------------------------------------------------------
def _trained(drive, name):
    reset_root()
    wf, _, _ = build(XLADevice(), layers(0.05, 0.0), name=name, steps=4)
    drive(wf)
    return params_of(wf)


def test_run_chunked_trains_the_table_as_run_does():
    plain = _trained(lambda wf: wf.run(), "xing_run")
    chunked = _trained(lambda wf: wf.run_chunked(2), "xing_chunked")
    assert set(plain) == set(chunked)
    for name, want in plain.items():
        assert rel(chunked[name], want) < 1e-5, name


@pytest.mark.parametrize("what", ["export_forward", "DecodeModel"])
def test_serving_refuses_the_table_by_name(what, tmp_path):
    reset_root()
    wf, _, _ = build(XLADevice(), layers(0.05, 0.0), name=f"xing_{what}")
    from znicz_tpu.export import refuse_unserved
    with pytest.raises(NotImplementedError) as said:
        if what == "export_forward":
            wf.export_forward(str(tmp_path / "bundle.npz"))
        else:
            refuse_unserved(wf.forwards, "DecodeModel")
    assert "stream_open" in str(said.value)        # the first such layer
    assert "4 streams" in str(said.value)
    units = [u for u in wf.forwards
             if isinstance(u, attention.MultiHeadAttention)]
    with pytest.raises(NotImplementedError) as said:
        refuse_unserved(units, what)
    for word in ("kv_latent", "q_latent", "score_scale", "R5"):
        assert word in str(said.value)


def test_the_four_units_share_one_family_in_the_program_s_map():
    """``observe.op_scopes()`` files the open, the READs, the WRITEs and
    the close, forward and backward, under ONE family, ``Streams`` — a
    name no row of the benchmark's ``FAMILIES`` takes."""
    from znicz_tpu import observe
    from znicz_tpu.ops.nn_units import family_of
    reset_root()
    wf, _, _ = build(XLADevice(), layers(0.05, 0.0), name="xing_scopes")
    wf.run()
    pairs = {family_of(unit) for unit in list(wf.forwards) + list(wf.gds)
             if "Stream" in type(unit).__name__}
    assert pairs == {("Streams", False), ("Streams", True)}
    kinds = {type(unit).__name__ for unit in list(wf.forwards)
             + list(wf.gds) if "Stream" in type(unit).__name__}
    assert len(kinds) == 8
    families = set()
    for program in observe.op_scopes().values():
        for entry in program.values():
            families.update([entry.get("family")]
                            + list(entry.get("families", ())))
    assert "Streams" in families
    for gd in wf.gds:               # nothing of a trace is left behind
        assert getattr(gd, "err_stream", None) is None
