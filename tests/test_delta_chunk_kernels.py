"""What is local to a chunk of the gated delta rule as two kernels
(``znicz_gdr_chunk_fwd`` / ``_bwd``, PR 32), interpreted, against
``pallas_delta.chunk_local`` in ``jax.numpy`` and its ``jax.vjp``: the
cell's widths and a pair of lane multiples, f32 and bf16 products,
decay so strong that a difference of prefixes would lose its digits,
keys nearly parallel (the inverse's hard case), a last chunk that is
mostly padding, chunks a grid step that do and do not divide the chunk
count — and that the limit the f32 cases meet is one a bf16 inverse or
a bf16 Γ would not (the Olmo-Hybrid cell's ``correct`` cannot tell;
PERF.md §7).  And, since PR 41, the kernels' products with a 0 / ±1
matrix (``pallas_delta._mask_product``: the f32 factor's three bf16
parts in one contraction): against float64 beside a product at the
highest precision, ONE bf16 part refused by the same f32 limit, the
bodies' jaxprs counted against ``pallas_delta.chunk_products``, and
the unit's two gauge stats.

(The rule through these kernels against the token-by-token recurrence:
``tests/test_pallas_delta.py``; the unit: ``tests/test_delta_net.py``.)
"""

import collections
import functools
import math

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest

from tests import test_qkv_prep_kernel as tq
from znicz_tpu.observe import metrics as obs_metrics
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


@pytest.mark.parametrize("rounded", ["inverse", "gamma", "log_alpha"])
def test_the_f32_limit_refuses_a_bf16_inverse_and_a_bf16_gamma(
        rounded, monkeypatch):
    """The same comparison, the same limit, ``chunk_local`` with ONE
    of its f32 matrices rounded to bf16: not within 1e-5, forward or
    backward — a kernel that took that shortcut would fail the cases
    above, where the cell's one ``layers`` limit would pass it.  And
    the KERNELS with ONE bf16 part of the f32 factor in their mask
    products — the cheap wrong way to three passes — where the three
    parts pass (``cell_widths`` above is this very comparison)."""
    rows = _inputs(96, 192, "mixed")

    def bf16(a):
        return a.astype(jnp.bfloat16).astype(jnp.float32)

    exact_inverse, exact_exp = pd.unit_lower_inverse, jnp.exp

    def run(local=pd.chunk_local):
        with jax.default_matmul_precision("highest"):
            outputs, pullback = jax.vjp(local, *rows)
            return outputs, pullback(_cotangents(outputs))

    want = run()
    if rounded == "inverse":
        monkeypatch.setattr(pd, "unit_lower_inverse",
                            lambda lower: bf16(exact_inverse(lower)))
        got = run()
    elif rounded == "gamma":    # the one exponential of a (…, C, C) array
        monkeypatch.setattr(
            pd.jnp, "exp",
            lambda a: bf16(exact_exp(a)) if a.ndim == 4
            and a.shape[-1] == a.shape[-2] else exact_exp(a))
        got = run()
    else:
        whole = pd._three_parts
        monkeypatch.setattr(pd, "_three_parts", lambda x: tuple(
            part if at == 0 else jnp.zeros_like(part)
            for at, part in enumerate(whole(x))))
        jax.clear_caches()      # the bodies' jitted functions of values
        got = run(functools.partial(pd.chunk_local_kernels,
                                    interpret=True, block=4))
        jax.clear_caches()
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


# ----------------------------------------------------------------------
# products with a 0 / ±1 matrix: three bf16 parts in one contraction
# ----------------------------------------------------------------------
def _masks(kind, n):
    """``n`` (C, C) matrices of 0/1 (lower triangles whose diagonal
    moves) or 0/±1 (``_kda_positions``' signed reach) entries."""
    at = np.arange(C)
    lower = [(at[None, :] <= at[:, None] - m).astype(np.float32)
             for m in range(n)]
    if kind == "0/1":
        return lower
    return [np.triu(np.ones((C, C), np.float32), 16 * (m + 1)) - one
            for m, one in enumerate(lower)]


@pytest.mark.parametrize("spread", ["log_alpha", "cotangent"])
@pytest.mark.parametrize("stacked", [1, 7])
@pytest.mark.parametrize("side", ["left", "right", "transposed"])
@pytest.mark.parametrize("kind", ["0/1", "0/±1"])
def test_a_mask_product_is_a_product_at_the_highest_precision(
        kind, side, stacked, spread):
    """``mask @ x`` from the three bf16 parts of x against the float64
    product: as near it as ``precision=HIGHEST`` on the same inputs —
    both sum exact terms in f32 and only the order differs, so "as
    near" is within twice its error or f32's last bit of Σ |terms| —
    for log α's range and a cotangent's 34 decades of both signs; the
    parts add back to x bit for bit; ONE part is 1,000 times off."""
    rng = np.random.default_rng(17)
    masks = _masks(kind, stacked)
    shape = {"left": (C, 128), "right": (stacked * C, C),
             "transposed": (stacked * C, 128)}[side]
    x = rng.uniform(-5.0, 0.0, shape) if spread == "log_alpha" \
        else rng.choice([-1.0, 1.0], shape) * 10.0 ** rng.uniform(
            -30.0, 4.0, shape)
    x = x.astype(np.float32)
    mask = {"left": np.concatenate(masks, axis=0),       # (n C, C) · x
            "right": np.concatenate(masks, axis=1),      # x · (C, n C)
            "transposed": np.concatenate(                # Σ_m P_mᵀ y_m
                [m.T for m in masks], axis=1)}[side]
    right = side == "right"
    a, b = (x, mask) if right else (mask, x)
    parts = jax.jit(pd._three_parts)(x)
    assert all(part.dtype == jnp.bfloat16 for part in parts)
    np.testing.assert_array_equal(
        sum(np.asarray(part, np.float32) for part in parts), x)
    got = jax.jit(lambda x: pd._mask_product(
        pd._for_mask_product(jnp.asarray(mask), right), x, right))(x)
    highest = jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    assert got.dtype == jnp.float32 and got.shape == highest.shape
    want = a.astype(np.float64) @ b.astype(np.float64)
    # against what the terms could lose: Σ |terms| a row and column
    scale = np.abs(a).astype(np.float64) @ np.abs(b).astype(np.float64)
    scale = np.maximum(scale, np.finfo(np.float64).tiny)

    def error(product):
        return float((np.abs(np.asarray(product, np.float64) - want)
                      / scale).max())
    assert error(got) <= max(2.0 * error(highest), 2.0 ** -23), (
        error(got), error(highest))
    assert error(got) < 1e-6
    # bf16's eight bits, not f32's 24
    hi = np.asarray(parts[0], np.float64)
    assert error(hi @ mask if right else mask @ hi) > 1e-4


def _eqns(jaxpr, derived=frozenset()):
    """Every equation of a jaxpr and of those nested in it, each with
    whether its operands come from indices and literals alone
    (``iota`` and what is computed from it: a mask by construction) —
    a call's or a loop's operands map one to one onto its body's."""
    derived = set(derived)

    def known(v):
        return isinstance(v, jax.extend.core.Literal) or v in derived

    for eqn in jaxpr.eqns:
        inner = [getattr(value, "jaxpr", value)
                 for value in eqn.params.values()
                 if hasattr(getattr(value, "jaxpr", value), "eqns")]
        for body in inner:
            assert len(body.invars) == len(eqn.invars), eqn.primitive
            yield from _eqns(body, {new for new, old in zip(
                body.invars, eqn.invars) if known(old)})
        if not inner:
            yield eqn, [known(v) for v in eqn.invars]
            if all(known(v) for v in eqn.invars):
                derived.update(eqn.outvars)


def _body(channels, backward, dot_dtype):
    """The jaxpr of a chunk kernel's body and the chunks it holds in a
    basic block."""
    dk = dv = 128
    shapes = [(G, N, C, dk), (G, N, C, dk), (G, N, C, dv),
              (G, N, C, dk) if channels else (G, N, C), (G, N, C)]
    rows = [jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes]
    rule = functools.partial(pd.chunk_local_kernels, dot_dtype=dot_dtype,
                             interpret=True)
    if backward:
        def program(*rows):
            outputs, pullback = jax.vjp(rule, *rows)
            return pullback(outputs)
    else:
        program = rule
    name = f"znicz_{'kda' if channels else 'gdr'}_chunk_" \
        f"{'bwd' if backward else 'fwd'}"
    calls = [eqn for eqn in tq._nested(jax.make_jaxpr(program)(*rows).jaxpr)
             if eqn.primitive.name == "pallas_call"
             and eqn.params["name"] == name]
    assert len(calls) == 1, name
    return calls[0].params["jaxpr"], math.gcd(
        min(pd.CHUNKS_PER_STEP, G * N), pd._TOGETHER)


@pytest.mark.parametrize("dot_dtype", [jnp.bfloat16, None])
@pytest.mark.parametrize("backward", [False, True])
@pytest.mark.parametrize("channels", [False, True])
def test_a_body_s_products_are_the_count_s(channels, backward, dot_dtype):
    """Per kernel body: its ``dot_general``s at the highest precision
    are ``chunk_products``' exact ones and none of them has a factor
    built from indices — those go as three bf16 parts, one
    ``dot_general`` each with bf16 inputs, f32 out and the mask thrice
    along the contraction."""
    body, together = _body(channels, backward, dot_dtype)
    way = "bwd" if backward else "fwd"
    counts = pd.chunk_products(channels, C, dot_dtype=dot_dtype)
    exact = masked = 0
    for eqn, from_indices in _eqns(body):
        if eqn.primitive.name != "dot_general":
            continue
        if eqn.params["precision"] is not None:
            assert set(eqn.params["precision"]) == {
                jax.lax.Precision.HIGHEST}, eqn
            assert not any(from_indices), eqn
            exact += 1
        elif any(from_indices):
            assert not all(from_indices), eqn
            assert {v.aval.dtype for v in eqn.invars} == {
                jnp.dtype(jnp.bfloat16)}
            assert eqn.outvars[0].aval.dtype == jnp.float32
            side = from_indices.index(True)
            contracted = eqn.params["dimension_numbers"][0][side][0]
            assert eqn.invars[side].aval.shape[contracted] % (3 * C) == 0
            masked += 1
        else:       # W, U, P and their transposes: ``dot_dtype`` inputs
            assert dot_dtype is not None
    assert exact == together * counts[f"exact_{way}"], (exact, counts)
    assert masked == together * counts[f"mask_{way}"], (masked, counts)


engine = tq.engine      # the fixture: a fresh ``root.common.engine``


@pytest.mark.parametrize("decay", ["head", "channel"])
def test_the_gauge_says_what_a_chunk_multiplies(decay, engine):
    channels = decay == "channel"
    unit = tq._unit(128, 128, **(tq.CHANNEL if channels else {}))
    assert not unit._kernels
    for stat in ("exact_products", "mask_products"):
        assert obs_metrics.delta_scan("mixer", stat).value == 0.0
        assert stat in obs_metrics.delta_scan.__doc__
    tq._kernels_on(engine)
    unit = tq._unit(128, 128, **(tq.CHANNEL if channels else {}))
    counts = pd.chunk_products(channels, unit.chunk,
                               dot_dtype=unit.mxu_dtype)
    assert obs_metrics.delta_scan("mixer", "exact_products").value \
        == counts["exact_fwd"] + counts["exact_bwd"]
    assert obs_metrics.delta_scan("mixer", "mask_products").value == 3
    # at the cells' chunk of 64: Ling's 23 + 3, Olmo-Hybrid's 11 + 3
    cell = pd.chunk_products(channels)
    assert cell["exact_fwd"] + cell["exact_bwd"] == (23 if channels
                                                     else 11)
