"""``post_norm`` — the norm on a sublayer's OUTPUT inside the skip,
x + RMSNorm(f(x)) (PR 31) — on ``MultiHeadAttention`` and ``GatedMLP``:
against a hand-written block and against the numpy oracles (forward,
err_input, every parameter after two momentum steps); with ``post_norm``
unset the jaxprs of both units are what they were at the parent of
PR 31; serving refuses the option by name.  (Refused together with
``pre_norm``: ``tests/test_delta_net.py``, beside the new unit's.)"""

import collections
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.test_delta_net import D, _agree, _forward_of
from znicz_tpu.backends import XLADevice
from znicz_tpu.dummy import DummyUnit, DummyWorkflow
from znicz_tpu.memory import Vector
from znicz_tpu.ops import attention, moe
from znicz_tpu.ops.rms_norm import rms_norm
from znicz_tpu.utils import prng


def test_post_norm_attention_is_x_plus_norm_of_the_sublayer():
    rng = np.random.default_rng(2)
    x = rng.normal(size=(2, 16, D)).astype(np.float32)
    options = dict(n_heads=4, causal=True, include_bias=False,
                   qk_norm="rms", norm_eps=1e-6)
    post, got = _forward_of(lambda wf: attention.MultiHeadAttention(
        wf, **options, post_norm="rms", residual=True), x)
    bare, _ = _forward_of(lambda wf: attention.MultiHeadAttention(
        wf, **options), x)
    for attr in ("weights", "weights_out", "gain_q", "gain_k"):
        vec = getattr(bare, attr)
        vec.reset(np.array(getattr(post, attr).mem))
        vec.initialize(bare.device)
    f_x = bare.xla_forward(*bare.forward_args())
    want = x + rms_norm(jnp, f_x, post.gain_norm.mem, 1e-6)
    assert post.gain_norm.shape == (D,) and not bare.gain_norm
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_post_norm_gated_mlp_is_x_plus_norm_of_the_sublayer():
    rng = np.random.default_rng(4)
    x = rng.normal(size=(2, 16, D)).astype(np.float32)
    post, got = _forward_of(lambda wf: moe.GatedMLP(
        wf, width=24, post_norm="rms", residual=True, norm_eps=1e-6), x)
    w_g, w_u, w_d = (np.asarray(v.mem) for v in (
        post.weights, post.weights_up, post.weights_down))
    gate = x @ w_g
    f_x = ((gate / (1 + np.exp(-gate))) * (x @ w_u)) @ w_d
    want = x + rms_norm(np, f_x, post.gain_norm.mem, 1e-6)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("kind", ["attention", "gated_mlp"])
def test_post_norm_xla_against_the_numpy_oracle(kind):
    if kind == "attention":
        make = lambda wf: attention.MultiHeadAttention(    # noqa: E731
            wf, n_heads=4, causal=True, include_bias=False,
            qk_norm="rms", post_norm="rms", residual=True, norm_eps=1e-6)
        pair = attention.GDMultiHeadAttention
    else:
        make = lambda wf: moe.GatedMLP(                    # noqa: E731
            wf, width=24, post_norm="rms", residual=True, norm_eps=1e-6)
        pair = moe.GDGatedMLP
    _, drawn = _agree(make, pair)
    assert "gain_norm" in drawn


#: jaxpr of ``xla_forward`` at the parent of PR 31 (d210be8), (2, 16, 32)
#: input: equations, and a digest of the printed jaxpr
PARENT_JAXPRS = {
    "attention_block": (60, "80e6b8963b28dc0a"),
    "attention_bare": (36, "9305555d115c4a86"),
    "gated_mlp_block": (20, "54c7e55a8cddf429"),
    "gated_mlp_bare": (10, "026f8e9fde6a0cd8"),
}
UNSET = {
    "attention_block": lambda wf: attention.MultiHeadAttention(
        wf, n_heads=4, causal=True, include_bias=False, pre_norm="rms",
        qk_norm="rms", residual=True, norm_eps=1e-6),
    "attention_bare": lambda wf: attention.MultiHeadAttention(
        wf, n_heads=4, causal=True),
    "gated_mlp_block": lambda wf: moe.GatedMLP(
        wf, width=40, pre_norm="rms", residual=True, norm_eps=1e-6),
    "gated_mlp_bare": lambda wf: moe.GatedMLP(wf, width=40),
}


@pytest.mark.parametrize("case", list(PARENT_JAXPRS))
def test_with_post_norm_unset_the_program_is_what_it_was(case):
    prng.seed_all(5)
    wf = DummyWorkflow()
    x = np.random.default_rng(0).normal(0, 1, (2, 16, D)).astype(
        np.float32)
    fwd = UNSET[case](wf)
    fwd.link_attrs(DummyUnit(wf, output=Vector(x, name="x")),
                   ("input", "output"))
    fwd.initialize(device=XLADevice())
    jaxpr = jax.make_jaxpr(fwd.xla_forward)(*fwd.forward_args())
    equations, digest = PARENT_JAXPRS[case]
    assert len(jaxpr.jaxpr.eqns) == equations, collections.Counter(
        e.primitive.name for e in jaxpr.jaxpr.eqns)
    assert hashlib.sha256(str(jaxpr).encode()).hexdigest()[:16] == digest


def test_serving_refuses_post_norm_by_name():
    from znicz_tpu.export import refuse_unserved
    unit = attention.MultiHeadAttention(DummyWorkflow(), n_heads=2,
                                        post_norm="rms")
    with pytest.raises(NotImplementedError, match="sets post_norm"):
        refuse_unserved([unit], "DecodeModel")
    refuse_unserved([attention.MultiHeadAttention(DummyWorkflow(),
                                                  n_heads=2)], "export")
