"""What is local to a chunk of the gated delta rule as two kernels
(``znicz_gdr_chunk_fwd`` / ``_bwd``, PR 32), interpreted, against
``pallas_delta.chunk_local`` in ``jax.numpy`` and its ``jax.vjp``: the
cell's widths and a pair of lane multiples, f32 and bf16 products,
decay so strong that a difference of prefixes would lose its digits,
keys nearly parallel (the inverse's hard case), a last chunk that is
mostly padding, chunks a grid step that do and do not divide the chunk
count — and that the limit the f32 cases meet is one a bf16 inverse or
a bf16 Γ would not (the Olmo-Hybrid cell's ``correct`` cannot tell;
PERF.md §7).

(The rule through these kernels against the token-by-token recurrence:
``tests/test_pallas_delta.py``; the unit: ``tests/test_delta_net.py``.)
"""

import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from znicz_tpu.ops import pallas_delta as pd

G, N, C = 2, 3, 64
F32_LIMIT = 1e-5
#: name → (d_k, d_v, dot_dtype, regime, chunks a grid step)
CASES = {
    "cell_widths": (96, 192, None, "mixed", 4),
    "lane_multiples": (128, 256, None, "mixed", 4),
    "bf16_products": (96, 192, jnp.bfloat16, "mixed", 4),
    "strong_decay": (96, 192, None, "strong_decay", 4),
    "parallel_keys": (96, 192, None, "parallel_keys", 4),
    "padded_tail": (96, 192, None, "padded_tail", 4),
    "steps_divide_the_chunks": (24, 40, None, "mixed", 3),
    "one_step_holds_them_all": (24, 40, None, "mixed", 8),
}


def _inputs(dk, dv, regime):
    rng = np.random.default_rng(5)

    def draw(*shape):
        return rng.normal(size=shape).astype(np.float32)

    q = draw(G, N, C, dk) * dk ** -0.5
    k = draw(G, N, C, dk)
    if regime == "parallel_keys":      # as alike as one direction + 2%
        k = draw(G, N, 1, dk) + 0.02 * k
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = draw(G, N, C, dv)
    log_alpha = -np.exp(rng.uniform(-6.0, 0.0, (G, N, C)))
    beta = rng.uniform(0.0, 2.0, (G, N, C))
    if regime == "strong_decay":       # prefixes reach −320
        log_alpha = rng.uniform(-5.5, -4.5, (G, N, C))
    if regime == "parallel_keys":
        beta = rng.uniform(1.8, 2.0, (G, N, C))
    if regime == "padded_tail":        # positions that write nothing
        for a in (q, k, v, log_alpha, beta):
            a[:, -1, 24:] = 0.0
    return tuple(jnp.asarray(a, jnp.float32)
                 for a in (q, k, v, log_alpha, beta))


def _cotangents(outputs):
    rng = np.random.default_rng(9)
    return tuple(jnp.asarray(rng.normal(size=o.shape), jnp.float32)
                 for o in outputs)


@functools.cache
def _both_ways(dot_dtype, block):
    """(plain, kernels): each ``rows → (outputs, pullback's result for
    fixed cotangents)`` as ONE compiled program."""
    def program(local):
        def run(rows, cotangents):
            with jax.default_matmul_precision("highest"):
                outputs, pullback = jax.vjp(local, *rows)
                return outputs, pullback(cotangents)
        return jax.jit(run)
    return (program(functools.partial(pd.chunk_local,
                                      dot_dtype=dot_dtype)),
            program(functools.partial(pd.chunk_local_kernels,
                                      dot_dtype=dot_dtype, interpret=True,
                                      block=block)))


def _worst(got, want) -> dict:
    """Largest difference over the largest entry, per array."""
    return {name: float(jnp.abs(a - b).max() / jnp.abs(b).max())
            for name, a, b in zip(got._fields, got, want)}


Outputs = collections.namedtuple("Outputs", "w k_hat u decay qc p")
Gradients = collections.namedtuple("Gradients", "q k v log_alpha beta")


@functools.cache
def _run(case):
    dk, dv, dot_dtype, regime, block = CASES[case]
    rows = _inputs(dk, dv, regime)
    plain, kernels = _both_ways(dot_dtype, block)
    cotangents = _cotangents(jax.eval_shape(
        functools.partial(pd.chunk_local, dot_dtype=dot_dtype), *rows))
    return plain(rows, cotangents), kernels(rows, cotangents), dot_dtype


@pytest.mark.parametrize("case", list(CASES))
def test_forward_kernel_against_chunk_local(case):
    (want, _), (got, _), dot_dtype = _run(case)
    for o in got:
        assert o.dtype == jnp.float32 and bool(jnp.isfinite(o).all())
    assert got[3].shape == (G, N)
    # bf16 products round the same inputs at the same places: the
    # difference is the accumulation's order, not bf16's eight bits
    limit = F32_LIMIT if dot_dtype is None else 1e-4
    worst = _worst(Outputs(*got), Outputs(*want))
    assert max(worst.values()) <= limit, worst
    assert not np.triu(np.asarray(got[5]), 1).any()        # P is lower


@pytest.mark.parametrize("case", list(CASES))
def test_backward_kernel_against_chunk_local_s_vjp(case):
    (_, want), (_, got), dot_dtype = _run(case)
    # under bf16 products autodiff rounds each cotangent to bf16 on its
    # way through a product; the kernel keeps them f32 between products
    limit = F32_LIMIT if dot_dtype is None else 2e-2
    worst = _worst(Gradients(*got), Gradients(*want))
    assert max(worst.values()) <= limit, worst
    for g in got:
        assert float(jnp.abs(g).max()) > 0


@pytest.mark.parametrize("rounded", ["inverse", "gamma"])
def test_the_f32_limit_refuses_a_bf16_inverse_and_a_bf16_gamma(
        rounded, monkeypatch):
    """The same comparison, the same limit, ``chunk_local`` with ONE
    of its f32 matrices rounded to bf16: not within 1e-5, forward or
    backward — a kernel that took that shortcut would fail the cases
    above, where the cell's one ``layers`` limit would pass it."""
    rows = _inputs(96, 192, "mixed")

    def bf16(a):
        return a.astype(jnp.bfloat16).astype(jnp.float32)

    exact_inverse, exact_exp = pd.unit_lower_inverse, jnp.exp

    def run():
        with jax.default_matmul_precision("highest"):
            outputs, pullback = jax.vjp(pd.chunk_local, *rows)
            return outputs, pullback(_cotangents(outputs))

    want = run()
    if rounded == "inverse":
        monkeypatch.setattr(pd, "unit_lower_inverse",
                            lambda lower: bf16(exact_inverse(lower)))
    else:       # Γ is the one exponential of a (…, C, C) array
        monkeypatch.setattr(
            pd.jnp, "exp",
            lambda a: bf16(exact_exp(a)) if a.ndim == 4
            and a.shape[-1] == a.shape[-2] else exact_exp(a))
    got = run()
    monkeypatch.undo()
    forward = _worst(Outputs(*got[0]), Outputs(*want[0]))
    backward = _worst(Gradients(*got[1]), Gradients(*want[1]))
    assert max(forward.values()) > 10 * F32_LIMIT, forward
    assert max(backward.values()) > 10 * F32_LIMIT, backward


def test_kernels_take_any_whole_number_of_chunks_a_step():
    """``block`` beyond the chunk count is the chunk count; the
    kernel's names are not the walk's (``delta_ms_per_step`` matches
    ``znicz_delta``)."""
    rows = _inputs(24, 40, "mixed")
    few = pd.chunk_local_kernels(*rows, interpret=True, block=64)
    for a, b in zip(few, pd.chunk_local(*rows)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
    text = str(jax.make_jaxpr(
        lambda *a: jax.vjp(pd.chunk_local_kernels, *a)[1](few))(*rows))
    assert "znicz_gdr_chunk_fwd" in text and "znicz_gdr_chunk_bwd" in text
    assert "znicz_delta" not in text
