"""Round-19 SDC sentinel tests: fingerprints, votes, audits,
quarantine — plus the satellite series (rows-quarantined, build_info).

The multi-process gang drill (vote localizes a flipped process,
culprit blocklisted, pre-divergence resume, bitwise parity) runs as
the ``GRAFT_CHAOS=1 __graft_entry__.py sdc`` dryrun; these tests pin
every layer the drill composes, fast and in-process.
"""

from __future__ import annotations

import math
import os
import re

import numpy as np
import pytest

from znicz_tpu.backends import XLADevice
from znicz_tpu.loader.fullbatch import ArrayLoader
from znicz_tpu.models.standard_workflow import StandardWorkflow
from znicz_tpu.observe import metrics as obs_metrics
from znicz_tpu.resilience import integrity
from znicz_tpu.utils import prng
from znicz_tpu.utils.config import root

pytestmark = pytest.mark.usefixtures("reset_engine_config")


@pytest.fixture()
def reset_engine_config():
    yield
    root.common.engine.faults = None
    root.common.engine.sdc_fingerprints = True
    root.common.engine.sdc_vote_interval = 50
    root.common.engine.sdc_audit_interval = 0
    root.common.engine.sdc_suspect_threshold = 1


def _counter(family: str, **labels) -> float:
    fam = obs_metrics.REGISTRY.get(family)
    if fam is None:
        return 0.0
    want = tuple(str(labels[n]) for n in fam.labelnames)
    for key, child in fam.items():
        if key == want:
            return float(child.value)
    return 0.0


def _build(name: str, snapshot_dir: str | None = None,
           max_epochs: int = 2, seed: int = 17):
    rng = np.random.default_rng(0)
    data = rng.normal(size=(96, 10)).astype(np.float32)
    labels = (rng.random(96) * 3).astype(np.int32)
    prng.seed_all(seed)
    snap = None if snapshot_dir is None else {
        "directory": snapshot_dir, "prefix": "sdc"}
    wf = StandardWorkflow(
        name=name,
        loader_factory=lambda w: ArrayLoader(
            w, train_data=data[:72], train_labels=labels[:72],
            valid_data=data[72:], valid_labels=labels[72:],
            minibatch_size=12),
        layers=[{"type": "all2all_tanh",
                 "->": {"output_sample_shape": 16},
                 "<-": {"learning_rate": 0.05, "gradient_moment": 0.9}},
                {"type": "softmax", "->": {"output_sample_shape": 3},
                 "<-": {"learning_rate": 0.05, "gradient_moment": 0.9}}],
        decision_config={"max_epochs": max_epochs},
        snapshotter_config=snap)
    wf._max_fires = 10 ** 6
    wf.initialize(device=XLADevice())
    return wf


# ----------------------------------------------------------------------
# fingerprint algebra
# ----------------------------------------------------------------------
def test_tensor_fingerprint_numpy_jax_agree():
    import jax.numpy as jnp
    rng = np.random.default_rng(3)
    for shape in ((5,), (16, 16), (3, 4, 5), (1000,)):
        arr = rng.normal(size=shape).astype(np.float32)
        a = float(integrity.tensor_fingerprint(np, arr))
        b = float(integrity.tensor_fingerprint(jnp, jnp.asarray(arr)))
        assert abs(a - b) <= 1e-4 * max(abs(a), 1.0), (shape, a, b)


def test_tensor_fingerprint_samples_element_zero():
    """The drill's flip target (element 0) must ALWAYS be sampled."""
    arr = np.zeros(10_000, dtype=np.float32)
    base = float(integrity.tensor_fingerprint(np, arr))
    arr[0] = 1000.0
    assert float(integrity.tensor_fingerprint(np, arr)) != base


def test_tensor_fingerprint_position_sensitive():
    a = np.zeros(128, dtype=np.float32)
    b = np.zeros(128, dtype=np.float32)
    a[0], a[2] = 1.0, 2.0   # both sampled at stride 2
    b[0], b[2] = 2.0, 1.0   # swapped values must not cancel
    assert float(integrity.tensor_fingerprint(np, a)) \
        != float(integrity.tensor_fingerprint(np, b))


def _flat_view_fingerprint(xp, arr):
    """The fold as it was defined before PR 26 — samples through a
    flat view of the whole tensor: the reference the sampler that
    reads them where they lie is held to."""
    flat = xp.ravel(arr).astype(xp.float32)
    sample = flat[::max(1, int(flat.shape[0]) // 64)]
    weights = 1.0 + (xp.arange(sample.shape[0], dtype=xp.float32)
                     % 31.0)
    return xp.sum(sample * weights)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape", [
    (5,), (1000,), (16, 16), (7, 13), (3, 4, 5),
    (4, 64, 32),     # slab-like: the stride is a whole expert
    (3, 3, 8, 16),
    (2, 3, 7),       # fewer than 64 elements: every one is sampled
])
def test_tensor_fingerprint_reads_the_flat_views_samples(shape, dtype):
    """Same elements, same order, same weights as
    ``ravel(arr).astype(f32)[::max(1, n // 64)]``: bit for bit on
    numpy, to the numpy-vs-jnp tolerance on jnp."""
    import jax.numpy as jnp
    arr = np.random.default_rng(5).normal(size=shape).astype(
        jnp.dtype(dtype))
    want = _flat_view_fingerprint(np, arr)
    got = integrity.tensor_fingerprint(np, arr)
    assert got.dtype == np.float32 and got == want, (got, want)
    on_jnp = float(integrity.tensor_fingerprint(jnp, jnp.asarray(arr)))
    assert abs(on_jnp - float(want)) <= 1e-4 * max(abs(float(want)), 1.0)
    n = math.prod(shape)
    assert np.array_equal(
        np.ravel_multi_index(integrity.sample_positions(shape), shape),
        np.arange(0, n, max(1, n // 64)))


def _all_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            inner = getattr(param, "jaxpr", param)
            if hasattr(inner, "eqns"):
                yield from _all_eqns(inner)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_jnp_fold_has_no_tensor_sized_equation(dtype):
    """No reshape of the operand, no whole-tensor convert: every
    equation of the traced fold is sample-sized."""
    import jax
    import jax.numpy as jnp
    operand = jnp.zeros((4, 64, 32), jnp.dtype(dtype))
    closed = jax.make_jaxpr(
        lambda a: integrity.tensor_fingerprint(jnp, a))(operand)
    sized = [(eqn.primitive.name, var.aval.shape)
             for eqn in _all_eqns(closed.jaxpr) for var in eqn.outvars
             if var.aval.size >= operand.size]
    assert not sized, sized


@pytest.fixture(scope="module")
def v5e_chip():
    """One chip of a DESCRIBED v5e (compile only, nothing runs).  The
    call loads libtpu, so it is made here, never at import."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no libtpu here, or another process has it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


_RELAYOUT = re.compile(
    r"= \w+\[([\d,]*)\]\{\S*\} (copy|reshape|transpose)\(")


@pytest.mark.parametrize("grad_dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape", [(4, 64, 32), (16, 128, 256),
                                   (512, 384)])
def test_fold_compiled_for_v5e_rewrites_no_tensor(v5e_chip, shape,
                                                  grad_dtype):
    """The three folds of ``_apply_param_xla`` around a momentum
    update, compiled for the chip: the tiled layout makes a flat view
    a whole-tensor ``copy`` (or a physical ``reshape``) there, three
    per parameter per step — the optimised program must hold none."""
    import jax
    import jax.numpy as jnp

    def step(w, acc, grad, fp):
        fp = fp.at[2].add(integrity.tensor_fingerprint(jnp, w))
        fp = fp.at[1].add(integrity.tensor_fingerprint(jnp, grad))
        acc = 0.9 * acc - 1e-4 * grad.astype(jnp.float32)
        w = w + acc
        fp = fp.at[0].add(integrity.tensor_fingerprint(jnp, w))
        return w, acc, fp

    def struct(shp, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shp, dtype, sharding=v5e_chip)

    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:   # a described chip's executable cannot be read back here
        text = jax.jit(step, donate_argnums=(0, 1, 3)).lower(
            struct(shape), struct(shape),
            struct(shape, jnp.dtype(grad_dtype)),
            struct((5,))).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
    assert "T(8,128)" in text or "T(4,128)" in text   # a TPU program
    rewrites = [m.group(0) for m in _RELAYOUT.finditer(text)
                if math.prod(int(d) for d in m.group(1).split(",")
                             if d) >= math.prod(shape)]
    assert not rewrites, rewrites


_MOVE = re.compile(
    r"^(?:ROOT )?%\S+ = (\w+)\[([\d,]*)\]\{[^}]*\} "
    r"(copy|transpose|slice|concatenate|reshape)\(%([\w.\-]+)")

#: one attention layer of each LM cell: (batch, T, D, heads, options)
_ATTENTION_CELLS = {
    "attn_lm_t2048": (32, 2048, 512, 8, {}),
    "olmoe_t4096": (1, 4096, 2048, 16, dict(
        pre_norm="rms", residual=True, qk_norm="rms",
        rope={"theta": 10000.0}, include_bias=False)),
}


@pytest.mark.parametrize("cell", list(_ATTENTION_CELLS))
def test_attention_layer_compiled_for_v5e_moves_no_activation(
        v5e_chip, monkeypatch, cell):
    """One attention layer's forward + backward at a cell's shape
    (causal, bf16 operands), compiled for the chip: the flash kernels
    read q, k, v and write o and the three gradients in the
    projections' own layout, so between a projection and a
    ``znicz_flash_*`` call the program holds no top-level ``copy`` /
    ``transpose`` / ``slice`` / ``concatenate`` / physical ``reshape``
    of B·T·D elements or more (the parent held four head-major
    transposes per layer: 24 ``copy`` of 0.6 ms a step in the LM
    cell).  A ``slice`` that changes the element type is the bf16 cast
    of v beside OLMoE's f32 norms, not a move, and XLA's asynchronous
    ``copy-start`` / ``slice-start`` are its own prefetches into the
    fast memory space."""
    import jax
    import jax.numpy as jnp

    from znicz_tpu.dummy import DummyUnit, DummyWorkflow
    from znicz_tpu.memory import Vector
    from znicz_tpu.ops import attention, pallas_kernels
    b, t, d, heads, options = _ATTENTION_CELLS[cell]
    monkeypatch.setattr(pallas_kernels, "is_tpu_device",
                        lambda device: True)
    root.common.precision_type = "bfloat16"
    wf = DummyWorkflow()
    src = DummyUnit(wf, output=Vector(np.zeros((b, t, d), np.float32),
                                      name="x"))
    unit = attention.MultiHeadAttention(wf, n_heads=heads, causal=True,
                                        **options)
    unit.link_attrs(src, ("input", "output"))
    unit.initialize(device=XLADevice())
    assert unit._flash.runs
    assert (unit._flash.layout, unit._flash.head_pack) \
        == ("boundary", 128 // (d // heads))

    def struct(a):
        return None if a is None else jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=v5e_chip)

    def step(dy, *args):
        out, pullback = jax.vjp(unit.xla_forward, *args)
        return out, pullback(dy)

    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:   # a described chip's executable cannot be read back here
        text = jax.jit(step).lower(
            jax.ShapeDtypeStruct((b, t, d), jnp.float32,
                                 sharding=v5e_chip),
            *(struct(a) for a in unit.forward_args())) \
            .compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
    # T 2048 meets its keys in one K tile: the backward is one kernel
    backward = {1: ("znicz_flash_bwd",),
                2: ("znicz_flash_dq", "znicz_flash_dkv")}
    for kernel in ("znicz_flash_fwd",) + backward[unit._flash.backward]:
        assert f"%{kernel}" in text, kernel
    for kernel in backward[3 - unit._flash.backward]:
        assert f"%{kernel}" not in text, kernel
    entry = text[text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    dtype_of = {m.group(1): m.group(2) for m in re.finditer(
        r"%([\w.\-]+) = \(?(\w+)\[", text)}
    moves = []
    for line in entry.splitlines():
        m = _MOVE.match(line.strip())
        if m is None:
            continue
        dtype, dims, op, operand = m.groups()
        size = math.prod(int(n) for n in dims.split(",") if n)
        if size >= b * t * d and not (
                op == "slice" and dtype_of.get(operand) != dtype):
            moves.append(line.strip()[:160])
    assert not moves, moves


#: the two kinds of attention layer of ``smallthinker_train_1of8`` at the
#: cell's T: (options, the kernels the program has to hold)
#: … and the MiB of dq its one-pass backward keeps in VMEM
_LONG_CONTEXT_LAYERS = {
    "nope_full": (dict(rope=None),
                  ("znicz_flash_fwd", "znicz_flash_bwd"), 56.0),
    "rope_window_4096": (
        dict(rope={"theta": 1500000.0}, window=4096),
        ("znicz_flash_fwd_win", "znicz_flash_bwd_win"), 15.75),
}


@pytest.mark.parametrize("kind", list(_LONG_CONTEXT_LAYERS))
def test_long_context_attention_layers_compile_for_v5e_at_published_widths(
        v5e_chip, monkeypatch, kind):
    """SmallThinker's two attention layers at T 16,384 × 2,560, 28 query
    heads on 4 K/V heads of 128 (a group of SEVEN), bf16 operands,
    forward + backward, through Mosaic for a described v5e: the
    un-windowed causal call past ``WHOLE_BLOCK_K`` walks a K grid eight
    tiles deep, the window of 4,096 runs the banded kernels over a band
    nine tiles wide — shapes no other cell has, which the chip's
    compiler has to take (PR 50).  Each backward is ONE call (PR 55):
    the seven heads' 7 × 16 dq tiles of 1024 × 128 wait in 56 MiB of
    VMEM for their later K tiles, the band's in 7 × 9 slots of 512 ×
    128 — which Mosaic has to grant beside a step's own tiles — and no
    ``znicz_flash_dq`` / ``_dkv`` is left in the program."""
    import jax
    import jax.numpy as jnp

    from znicz_tpu.dummy import DummyUnit, DummyWorkflow
    from znicz_tpu.memory import Vector
    from znicz_tpu.ops import attention, pallas_attention, pallas_kernels
    b, t, d = 1, 16384, 2560
    options, kernels, resident_mib = _LONG_CONTEXT_LAYERS[kind]
    monkeypatch.setattr(pallas_kernels, "is_tpu_device",
                        lambda device: True)
    root.common.precision_type = "bfloat16"
    wf = DummyWorkflow()
    src = DummyUnit(wf, output=Vector(np.zeros((b, t, d), np.float32),
                                      name="x"))
    unit = attention.MultiHeadAttention(
        wf, n_heads=28, n_kv_heads=4, head_dim=128, causal=True,
        pre_norm="rms", residual=True, include_bias=False,
        norm_eps=1e-6, **options)
    unit.link_attrs(src, ("input", "output"))
    unit.initialize(device=XLADevice())
    plan = unit._flash
    assert plan.runs and plan.layout == "boundary"
    assert plan.n_heads // plan.n_kv_heads == 7 and plan.backward == 1
    assert plan.resident_dq == resident_mib * 2 ** 20 \
        <= pallas_attention.RESIDENT_DQ_VMEM
    if options.get("window"):
        assert pallas_attention.band_steps(t, 512, 512, 4096) == (9, 9)
    else:
        assert pallas_attention.grid_blocks(True, t, t) == (1024, 2048)

    def struct(a):
        return None if a is None else jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=v5e_chip)

    def step(dy, *args):
        out, pullback = jax.vjp(unit.xla_forward, *args)
        return out, pullback(dy)

    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:   # a described chip's executable cannot be read back here
        text = jax.jit(step).lower(
            jax.ShapeDtypeStruct((b, t, d), jnp.float32,
                                 sharding=v5e_chip),
            *(struct(a) for a in unit.forward_args())) \
            .compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
    for kernel in kernels:
        assert re.search(rf"%\w*{kernel}[._\d]* = ", text), kernel
    assert "znicz_flash_dq" not in text and "znicz_flash_dkv" not in text
    assert ("znicz_flash_fwd_win" in text) == bool(options.get("window"))
    assert ("znicz_flash_bwd_win" in text) == bool(options.get("window"))


def test_gated_delta_rule_compiled_for_v5e_keeps_a_chunk_in_vmem(v5e_chip):
    """The rule's forward + backward at the Olmo-Hybrid cell's shape
    (T 4,096, 30 heads of 96 × 192, bf16 products), compiled for the
    chip: Mosaic takes the four kernels at the real widths, and of the
    (heads, chunks, 64, 64) arrays a chunk's algebra is made of the
    program writes only what the kernels hand on — P, its bf16 cast
    and its cotangent (one product), and (I + L)⁻¹ — where
    ``jax.numpy`` under autodiff wrote sixty (PERF.md §6, PR 32)."""
    import jax
    import jax.numpy as jnp

    from znicz_tpu.ops import pallas_delta
    b, t, h, dk, dv = 1, 4096, 30, 96, 192

    def struct(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=v5e_chip)

    def step(do, *rows):
        out, pullback = jax.vjp(
            lambda *r: pallas_delta.gated_delta_rule(
                *r, kernel=True, dot_dtype=jnp.bfloat16), *rows)
        return out, pullback(do)

    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:   # a described chip's executable cannot be read back here
        text = jax.jit(step).lower(
            struct(b, t, h, dv), struct(b, t, h, dk), struct(b, t, h, dk),
            struct(b, t, h, dv), struct(b, t, h), struct(b, t, h)) \
            .compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
    for kernel in ("znicz_gdr_chunk_fwd", "znicz_gdr_chunk_bwd",
                   "znicz_delta_state_fwd", "znicz_delta_state_bwd"):
        assert re.search(rf"%\w*{kernel}", text), kernel
    entry = text[text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    chunk_matrices = re.compile(
        r"^(?:ROOT )?%\S+ = \w+\[(?:30,64|1920),64,64\]\S* (\w[\w\-]*)\(")
    written = [m.group(1) for m in map(
        chunk_matrices.match, map(str.strip, entry.splitlines())) if m]
    # a view costs nothing; copy-start / -done is XLA's own prefetch
    written = [op for op in written if op not in (
        "bitcast", "get-tuple-element", "copy-start", "copy-done")]
    assert sorted(written) == ["convert", "fusion"], written


#: (rows, groups, model width, expert width) of the two expert cells'
#: grouped matmuls: all 64 experts, and a held share's row buffer
_EXPERT_CELLS = {"olmoe_t4096": (32768, 64, 2048, 1024),
                 "laguna_held_t4096": (5120, 8, 3072, 1024)}


@pytest.mark.parametrize("cell", list(_EXPERT_CELLS))
def test_grouped_matmul_kernels_compiled_for_v5e_at_the_cells_shapes(
        v5e_chip, cell):
    """An expert's gated MLP through ``grouped_matmul(kernel=True)``,
    forward + backward at a cell's shape (bf16 rows, f32 slabs),
    compiled for the chip: Mosaic takes the three kernels at the tile
    rule's choice under the VMEM each asks for, the program holds no
    operation of JAX's library kernel, and the row gradients leave the
    kernels in bf16 (PERF.md §6, PR 34)."""
    import jax
    import jax.numpy as jnp

    from znicz_tpu.ops.moe import grouped_matmul
    rows, groups, d, f = _EXPERT_CELLS[cell]

    def struct(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    def mlp(x, w_up, w_down, sizes):
        hidden = grouped_matmul(x, w_up, sizes, True, False)
        return grouped_matmul(hidden.astype(x.dtype), w_down, sizes,
                              True, False)

    def step(dy, x, w_up, w_down, sizes):
        out, pullback = jax.vjp(
            lambda *args: mlp(*args, sizes), x, w_up, w_down)
        return out, pullback(dy)

    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:   # a described chip's executable cannot be read back here
        text = jax.jit(step).lower(
            struct((rows, d)), struct((rows, d), jnp.bfloat16),
            struct((groups, d, f)), struct((groups, f, d)),
            struct((groups,), jnp.int32)).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
    # (``znicz_tgmm`` returns a pair: the slabs and their squares' sums)
    calls = re.findall(
        r"%(\w*gmm[\w.]*) = \(?(\w+)\[[\d,]*\][^=]* custom-call", text)
    names = sorted(re.sub(r"[.\d]+$", "", name) for name, _ in calls)
    assert len(calls) == 6, calls         # 2 forward, 2 row, 2 weight
    assert all("znicz_" in name for name in names), names
    for kernel in ("znicz_gmm", "znicz_gmm_t", "znicz_tgmm"):
        assert sum(name.endswith(kernel) for name in names) == 2, names
    assert all(dtype == "bf16" for name, dtype in calls
               if re.sub(r"[.\d]+$", "", name).endswith("znicz_gmm_t"))


#: forward calls no attention-layer test above reaches: name → (operand
#: shapes, query heads, K/V heads, window, the form the chooser picks)
_FORWARD_FORMS = {
    "laguna_window_48_on_8": (
        ((1, 4096, 6144), (1, 4096, 1024), (1, 4096, 1024)), 48, 8, 512,
        ("carried", "lanes", "exp")),
    "laguna_full_72_on_8": (
        ((1, 4096, 9216), (1, 4096, 1024), (1, 4096, 1024)), 72, 8, None,
        ("carried", "lanes", "exp")),
    "head_major_dh96": (((2, 2048, 3 * 4 * 96),), 4, 4, None,
                        ("none", "lanes", "exp")),
    "head_major_dh192": (((1, 2048, 3 * 4 * 192),), 4, 4, None,
                         ("none", "lanes", "exp")),
}


@pytest.mark.parametrize("name", list(_FORWARD_FORMS) + ["ring_hop_pairs"])
def test_flash_forward_compiled_for_v5e_in_every_form(v5e_chip, name):
    """The flash forward's visit at the shapes beside the two attention
    layers above — a window, grouped queries, the head-major fallback
    at dh 96 / 192, the ring's 1024-long K tile with pairs of dh-64
    heads and traced offsets: Mosaic takes each form the chooser picks
    (``pallas_attention.forward_form``) inside the scoped VMEM, under
    the kernel's name (PERF.md §6, PR 36)."""
    import jax
    import jax.numpy as jnp

    from znicz_tpu.ops import pallas_attention as pa

    def struct(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    if name == "ring_hop_pairs":
        want, kernel = ("none", "lanes", "q"), "znicz_flash_fwd"
        assert tuple(pa.forward_form(1024, 1024, 1024, 64)) == want
        fn = jax.jit(lambda q, k, v, q_off, k_off: pa.ring_hop(
            q, k, v, q_off, k_off, True, 1024, 1024, pack=2))
        args = [struct((1, 4, 1024, 128))] * 3 + [struct((), jnp.int32)] * 2
    else:
        shapes, heads, kv_heads, window, want = _FORWARD_FORMS[name]
        t = shapes[0][1]
        dh = shapes[0][2] // (heads + 2 * kv_heads
                              if len(shapes) == 1 else heads)
        blocks = pa.grid_blocks(True, t, t) if window is None \
            else pa.band_blocks(t)
        assert tuple(pa.forward_form(t, *blocks, dh, window)) == want
        kernel = "znicz_flash_fwd" + ("_win" if window else "")
        fn = jax.jit(lambda *arrays: pa.flash_attention_rows(
            arrays, heads, causal=True, n_kv_heads=kv_heads,
            window=window))
        args = [struct(shape) for shape in shapes]
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:   # a described chip's executable cannot be read back here
        text = fn.lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
    calls = re.findall(r"%(znicz_flash_\w+?)(?:\.\d+)? = ", text)
    assert calls == [kernel], calls


def test_vote_verdict_clean_selfbad_majority_tie():
    v = integrity.vote_verdict([1.0, 1.0, 1.0], [1.0, 1.0, 1.0], 1e-3)
    assert v == {"divergent": False, "culprits": [], "self_bad": []}
    # self-evident culprit (claimed != its own host recompute)
    v = integrity.vote_verdict([1.0, 5.0], [1.0, 1.0], 1e-3)
    assert v["divergent"] and v["culprits"] == [1]
    # sticky on-device self-check localizes even when claimed == host
    v = integrity.vote_verdict([1.0, 5.0], [1.0, 5.0], 1e-3,
                               self_flags=[0.0, 2.0])
    assert v["divergent"] and v["culprits"] == [1]
    # majority vote with >= 3 voters
    v = integrity.vote_verdict([1.0, 1.0, 7.0], [1.0, 1.0, 7.0], 1e-3)
    assert v["culprits"] == [2]
    # 2-process tie with no self-evidence: everyone is suspect
    v = integrity.vote_verdict([1.0, 5.0], [1.0, 5.0], 1e-3)
    assert v["divergent"] and v["culprits"] == [0, 1]


# ----------------------------------------------------------------------
# in-region fold + host recompute
# ----------------------------------------------------------------------
def test_device_fold_matches_host_recompute_and_numpy_oracle():
    root.common.engine.sdc_vote_interval = 4
    wf = _build("fp_parity")
    wf.run()
    fp = wf.integrity.read_device_fingerprint()
    assert fp is not None and fp[0] != 0.0 and fp[3] == 0.0
    host = integrity.host_param_fingerprint(wf)
    assert abs(fp[0] - host) <= 1e-3 * max(abs(host), 1.0)
    assert _counter("znicz_sdc_votes_total", workflow="fp_parity",
                    verdict="clean") >= 2
    assert _counter("znicz_sdc_votes_total", workflow="fp_parity",
                    verdict="divergent") == 0

    # numpy backend folds the same algebra (the oracle path)
    from znicz_tpu.backends import NumpyDevice
    np_wf = _build("fp_parity_np")
    # rebuild on the numpy oracle backend instead
    prng.seed_all(17)
    np_wf2 = StandardWorkflow(
        name="fp_parity_np2",
        loader_factory=np_wf._loader_factory,
        layers=np_wf.layers_config,
        decision_config={"max_epochs": 1})
    np_wf2._max_fires = 10 ** 6
    np_wf2.initialize(device=NumpyDevice())
    np_wf2.run()
    fp_np = np_wf2.integrity.read_device_fingerprint()
    assert fp_np is not None and fp_np[0] != 0.0
    host_np = integrity.host_param_fingerprint(np_wf2)
    assert abs(fp_np[0] - host_np) <= 1e-3 * max(abs(host_np), 1.0)


# ----------------------------------------------------------------------
# detection: flip_param (sticky self-check + vote), flip_grad (audit)
# ----------------------------------------------------------------------
def test_flip_param_trips_sticky_selfcheck_and_vote(tmp_path):
    root.common.engine.sdc_vote_interval = 4
    root.common.engine.faults = {
        "sdc.flip_param": {"process": 0, "at": [6]}}
    wf = _build("flip_param", snapshot_dir=str(tmp_path))
    wf.run()
    fp = wf.integrity.read_device_fingerprint()
    assert fp is not None and fp[3] >= 1.0, \
        "on-device self-check never tripped"
    assert _counter("znicz_sdc_votes_total", workflow="flip_param",
                    verdict="divergent") >= 1
    assert _counter("znicz_sdc_detected_total", kind="vote") >= 1
    assert _counter("znicz_sdc_suspect_total", process="0",
                    device="-") >= 1


def test_flip_param_quarantine_rolls_back_to_pre_divergence(tmp_path):
    """Unsupervised single-process quarantine: the sentinel reloads
    the last-known-good (pre-divergence) snapshot and the run keeps
    going with finite, clean weights."""
    root.common.engine.sdc_vote_interval = 3
    root.common.engine.faults = {
        "sdc.flip_param": {"process": 0, "at": [14],
                           "factor": 2.0 ** 16}}
    rollbacks = _counter("znicz_recoveries_total", kind="sdc_rollback")
    wf = _build("flip_rollback", snapshot_dir=str(tmp_path),
                max_epochs=4)
    wf.run()
    assert _counter("znicz_recoveries_total", kind="sdc_rollback") \
        >= rollbacks + 1, "no pre-divergence rollback happened"
    assert _counter("znicz_sdc_quarantined_total", kind="host") >= 1
    wf.forwards[0].weights.map_read()
    w = np.asarray(wf.forwards[0].weights.mem)
    assert np.isfinite(w).all()
    assert np.abs(w).max() < 100.0, \
        "corrupted magnitude survived the rollback"


def test_flip_grad_caught_by_shadow_audit():
    root.common.engine.sdc_audit_interval = 3
    root.common.engine.faults = {
        "sdc.flip_grad": {"process": 0, "after": 4, "factor": 64.0}}
    wf = _build("flip_grad")
    wf.run()
    assert _counter("znicz_sdc_audits_total", workflow="flip_grad",
                    verdict="mismatch") >= 1
    assert _counter("znicz_sdc_audits_total", workflow="flip_grad",
                    verdict="match") >= 1, "no clean audits before"
    assert _counter("znicz_sdc_detected_total", kind="audit") >= 1


def test_clean_audits_do_not_false_alarm():
    root.common.engine.sdc_audit_interval = 2
    before = _counter("znicz_sdc_detected_total", kind="audit")
    wf = _build("audit_clean")
    wf.run()
    assert _counter("znicz_sdc_audits_total", workflow="audit_clean",
                    verdict="match") >= 3
    assert _counter("znicz_sdc_audits_total", workflow="audit_clean",
                    verdict="mismatch") == 0
    assert _counter("znicz_sdc_detected_total", kind="audit") == before


def test_audit_does_not_perturb_the_training_trajectory():
    """Audit-on ≡ audit-off weights bitwise (the shadow replay must
    not advance the live PRNG or touch live buffers)."""
    def weights(wf):
        out = []
        for fwd in wf.forwards:
            for vec in (fwd.weights, fwd.bias):
                vec.map_read()
                out.append(np.array(vec.mem, copy=True))
        return out

    root.common.engine.sdc_audit_interval = 3
    on_wf = _build("audit_on")
    on_wf.run()
    on = weights(on_wf)
    root.common.engine.sdc_audit_interval = 0
    off_wf = _build("audit_off")
    off_wf.run()
    off = weights(off_wf)
    for a, b in zip(on, off):
        np.testing.assert_array_equal(a, b)


# ----------------------------------------------------------------------
# anomaly-guard composition
# ----------------------------------------------------------------------
def test_guard_skip_does_not_false_alarm_selfcheck():
    """A NaN step (update skipped by the anomaly guard) keeps the
    claimed fingerprint consistent with the stored params — the SDC
    self-check must not fire on a guard skip."""
    root.common.engine.sdc_vote_interval = 4
    root.common.engine.faults = {
        "train.nonfinite_loss": {"at": [5]}}
    wf = _build("guard_mix")
    wf.run()
    assert _counter("znicz_recoveries_total", kind="anomaly_step") >= 1
    fp = wf.integrity.read_device_fingerprint()
    assert fp is not None and fp[3] == 0.0, \
        f"self-check false alarm on a guard-skipped step: {fp}"
    assert _counter("znicz_sdc_votes_total", workflow="guard_mix",
                    verdict="divergent") == 0


# ----------------------------------------------------------------------
# satellites: rows-quarantined counter + /readyz fold, build_info
# ----------------------------------------------------------------------
def test_rows_quarantined_counted_and_on_readyz(tmp_path):
    from znicz_tpu.loader.streaming import StreamingLoader, write_shards
    from znicz_tpu.web_status import WebStatusServer
    root.common.engine.read_backoff_s = 0.01
    root.common.engine.faults = {
        "loader.corrupt_shard": {"shard": 1, "after": 1}}
    rng = np.random.default_rng(5)
    data = rng.integers(0, 255, size=(128, 8), dtype=np.uint8)
    labels = (rng.random(128) * 4).astype(np.int32)
    shards = str(tmp_path / "shards")
    write_shards(shards, data[:96], labels[:96], valid_data=data[96:],
                 valid_labels=labels[96:], rows_per_shard=24)
    prng.seed_all(9)
    wf = StandardWorkflow(
        name="rows_quar",
        loader_factory=lambda w: StreamingLoader(
            w, shards, minibatch_size=12, prefetch_depth=2,
            normalization_scale=1 / 127.5, normalization_bias=-1.0),
        layers=[{"type": "softmax", "->": {"output_sample_shape": 4},
                 "<-": {"learning_rate": 0.05}}],
        decision_config={"max_epochs": 2})
    wf._max_fires = 10 ** 6
    wf.initialize(device=XLADevice())
    wf.run()
    wf.loader.stop()
    rows = _counter("znicz_loader_rows_quarantined_total",
                    loader=wf.loader.name)
    assert rows > 0, "zero-filled rows were not counted"
    server = WebStatusServer(port=0)
    try:
        report = server.readiness()
    finally:
        server.stop()
    assert report["loaders"][wf.loader.name]["rows_quarantined"] \
        == int(rows)
    # REPORT-ONLY: quarantined rows never flip the probe by themselves
    assert not any("quarantin" in r for r in report["reasons"])


def test_build_info_exported_on_metrics():
    import urllib.request

    from znicz_tpu.web_status import WebStatusServer
    XLADevice()  # full-label registration (platform/mesh/processes)
    server = WebStatusServer(port=0)
    try:
        scrape = urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}/metrics", timeout=30
        ).read().decode()
    finally:
        server.stop()
    live = [line for line in scrape.splitlines()
            if line.startswith("znicz_build_info")
            and line.rstrip().endswith(" 1")]
    assert len(live) == 1, f"expected exactly one live build_info " \
                           f"row, got {live}"
    import znicz_tpu
    assert f'version="{znicz_tpu.__version__}"' in live[0]
    assert 'jax="' in live[0] and 'platform="cpu"' in live[0]


# ----------------------------------------------------------------------
# supervisor: sdc loss kind, blocklist, pre-divergence resume
# ----------------------------------------------------------------------
_STUB = """\
import json, os, sys, time
sys.path.insert(0, {repo!r})
from znicz_tpu.resilience import supervisor as sup
pid = int(os.environ["ZNICZ_PROCESS_ID"])
attempt = int(os.environ["ZNICZ_ELASTIC_ATTEMPT"])
hb_dir = os.environ["ZNICZ_HEARTBEAT_DIR"]
w = sup.HeartbeatWriter(hb_dir, pid, interval_s=0.05).start()
w.annotate(resumed_step=9 if attempt else 0)
for step in range(1, 7):
    w.beat(step)
    time.sleep(0.05)
    if attempt == 0 and step == 3:
        # the gang's symmetric SDC verdict: everyone annotates, the
        # culprit (pid 1) exits EXIT_SDC, the healthy peer exits
        # EXIT_PEER_LOST (its next collective can never complete)
        w.annotate(sdc_culprits=[1],
                   sdc_last_good=os.environ["SDC_GOOD"],
                   sdc_detected={{"vote": 1}},
                   faults_injected=(
                       {{"sdc.flip_param": 1}} if pid == 1 else {{}}))
        w.stop()
        os._exit(sup.EXIT_SDC if pid == 1 else sup.EXIT_PEER_LOST)
w.stop()
"""


def test_gang_sdc_exit_blocklists_and_resumes_pre_divergence(tmp_path):
    import sys

    from znicz_tpu.resilience import supervisor as sup
    from znicz_tpu.utils.snapshotter import Snapshotter
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    snaps = tmp_path / "snaps"
    good = Snapshotter.write({"good": True}, str(snaps), "sdc", "e1")
    # a NEWER snapshot exists (written after the divergence) — the
    # supervisor must prefer the gang-attested pre-divergence one
    import time as _time
    _time.sleep(0.05)
    Snapshotter.write({"post": True}, str(snaps), "sdc", "e2")
    stub = tmp_path / "stub.py"
    stub.write_text(_STUB.format(repo=repo))

    def argv_for(pid, n_procs, attempt):
        return [sys.executable, str(stub)]

    before = _counter("znicz_host_losses_total", kind="sdc")
    det_before = _counter("znicz_sdc_detected_total", kind="vote")
    supv = sup.ElasticSupervisor(
        argv_for, n_processes=2, work_dir=str(tmp_path / "work"),
        snapshot_dir=str(snaps), snapshot_prefix="sdc",
        heartbeat_timeout_s=2.0, start_grace_s=30.0,
        poll_interval_s=0.05, drain_s=5.0, max_restarts=2,
        env={"SDC_GOOD": good})
    summary = supv.run()
    assert summary["ok"] and summary["restarts"] == 1
    assert summary["losses"] == {"sdc": 1}
    assert summary["final_processes"] == 1
    assert summary["blocklisted"] == [1]
    assert summary["sdc_culprits"] == [1]
    assert summary["resumed"] == "pre-divergence"
    assert summary["resume_snapshots"][1] == good, \
        "restart did not resume from the pre-divergence snapshot"
    assert _counter("znicz_host_losses_total", kind="sdc") \
        == before + 1
    assert _counter("znicz_sdc_detected_total", kind="vote") \
        == det_before + 1, "worker attestations not folded"
    assert summary["resumed_step"] == 9


@pytest.mark.parametrize("out_dtype", ["bfloat16", "float32"])
def test_short_conv_kernels_compile_for_v5e_at_published_widths(
        v5e_chip, out_dtype):
    """``znicz_short_conv_fwd`` / ``_bwd`` (PR 43) at LFM2's widths,
    T 4,096 × 3 · 2,048 columns, through Mosaic for the chip: the
    sublane rotations, the 16-row halo blocks at either width of y's
    cotangent and the VMEM the full-width blocks ask for are what
    interpret mode cannot refuse.  Here and not beside the kernels'
    other tests: only ONE test file of the suite may load libtpu."""
    import jax
    import jax.numpy as jnp
    from znicz_tpu.ops import pallas_short_conv

    def step(projected, taps, weight):
        def loss(projected, taps):
            y = pallas_short_conv.short_conv(projected, taps,
                                             jnp.dtype(out_dtype))
            return jnp.sum(y.astype(jnp.float32) * weight)
        return jax.value_and_grad(loss, (0, 1))(projected, taps)

    shapes = [jax.ShapeDtypeStruct(shape, jnp.float32, sharding=v5e_chip)
              for shape in ((1, 4096, 6144), (2048, 3), (1, 4096, 2048))]
    text = jax.jit(step).lower(*shapes).compile().as_text()
    assert text.count("tpu_custom_call") >= 2
    for name in ("znicz_short_conv_fwd", "znicz_short_conv_bwd"):
        assert name in text


def test_stream_pair_compiled_for_v5e_keeps_the_streams_where_they_lie(
        v5e_chip):
    """A READ + WRITE pair of the residual streams (PR 46) around a
    stand-in sublayer, forward and every gradient, at Xing4.0's widths
    — the stream (1, 4 · 3,584, 4,096) f32, position-minor — compiled
    for the chip: Sinkhorn's twenty iterations are ONE loop in the
    program, and no ``copy`` / ``transpose`` of n·D·T elements or more
    stands in it (stored (T, n·D), every unit's stream was copied into
    a position-minor layout, 224 MB each, and the cell's step asked for
    18.1 GB of the chip's 15.75).  The D-wide transposes of h and of f
    are the layout's price and are allowed."""
    import jax
    import jax.numpy as jnp

    from znicz_tpu.dummy import DummyWorkflow
    from znicz_tpu.ops import streams
    b, t, d, n = 1, 4096, 3584, 4
    read = streams.StreamRead(DummyWorkflow(), n_streams=n)

    def step(x, phi, bias, alpha, w, g):
        def loss(x, phi, bias, alpha, w):
            (h, h_post, h_res), _ = read.xla_forward(x, phi, bias, alpha)
            f = jnp.dot(h.astype(jnp.bfloat16).reshape(t, d),
                        w.astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32)
            out = streams.StreamWrite.xla_forward(
                f.reshape(b, t, d), x, h_post, h_res)
            return jnp.sum(out * g), out
        return jax.value_and_grad(loss, (0, 1, 2, 3, 4), has_aux=True)(
            x, phi, bias, alpha, w)

    shapes = [jax.ShapeDtypeStruct(shape, jnp.float32, sharding=v5e_chip)
              for shape in ((b, n * d, t), (n * d, 2 * n + n * n),
                            (2 * n + n * n,), (3,), (d, d),
                            (b, n * d, t))]
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:   # a described chip's executable cannot be read back here
        compiled = jax.jit(step).lower(*shapes).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
    text = compiled.as_text()
    assert len(re.findall(r" while\(", text)) in (1, 2, 3)
    entry = text[text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    moves = []
    for line in entry.splitlines():
        m = _MOVE.match(line.strip())
        if m is None:
            continue
        _dtype, dims, op, _operand = m.groups()
        size = math.prod(int(k) for k in dims.split(",") if k)
        if size >= n * d * t and op in ("copy", "transpose"):
            moves.append(line.strip()[:160])
    assert not moves, moves
    # x, g in; X′, dX out; and at most three more streams' worth between
    assert compiled.memory_analysis().temp_size_in_bytes \
        <= 3 * 4 * n * d * t


def test_query_latent_layer_compiled_for_v5e_at_published_widths(
        v5e_chip, monkeypatch):
    """The latent-K/V layer WITH its query latent (768; 512 + 64; 32
    heads of 128 + 64 / 128) and the given score scale, forward and
    backward at T 4,096 in bf16, through Mosaic for the chip: the
    two-width flash kernels take the up-projected queries as they took
    the fused projection's columns."""
    import jax
    import jax.numpy as jnp

    from znicz_tpu.dummy import DummyUnit, DummyWorkflow
    from znicz_tpu.memory import Vector
    from znicz_tpu.ops import attention, pallas_kernels
    b, t, d = 1, 4096, 3584
    monkeypatch.setattr(pallas_kernels, "is_tpu_device",
                        lambda device: True)
    root.common.precision_type = "bfloat16"
    wf = DummyWorkflow()
    src = DummyUnit(wf, output=Vector(np.zeros((b, t, d), np.float32),
                                      name="x"))
    unit = attention.MultiHeadAttention(
        wf, n_heads=32, causal=True, include_bias=False, pre_norm="rms",
        q_latent=768, kv_latent=512, qk_nope=128, qk_rope=64,
        v_head_dim=128, score_scale=0.14468, norm_eps=1e-6,
        rope={"theta": 10000, "yarn": {
            "factor": 64, "original_max_position_embeddings": 4096,
            "beta_fast": 32, "beta_slow": 1, "attention_factor": 1.0}})
    unit.link_attrs(src, ("input", "output"))
    unit.initialize(device=XLADevice())
    assert unit._flash.runs
    assert unit.weights.shape == (d, 768 + 512 + 64)
    assert unit.weights_q_up.shape == (768, 32 * 192)

    def struct(a):
        return None if a is None else jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=v5e_chip)

    def step(dy, *args):
        out, pullback = jax.vjp(unit.xla_forward, *args)
        return out, pullback(dy)

    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        text = jax.jit(step).lower(
            jax.ShapeDtypeStruct((b, t, d), jnp.float32,
                                 sharding=v5e_chip),
            *(struct(a) for a in unit.forward_args())) \
            .compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
    for kernel in ("znicz_flash_fwd_mla", "znicz_flash_bwd_mla"):
        assert kernel in text, kernel
    # T 4,096: a pair's dq is 6 MiB of VMEM, the backward ONE call
    assert unit._flash.backward_passes == 1
    assert "znicz_flash_bwd_mla_d" not in text


def test_latent_layer_compiled_for_v5e_at_t_16384(v5e_chip, monkeypatch):
    """kanana-2-30b-a3b's latent-K/V layer WITHOUT a query latent (2,048;
    512 + 64; 32 heads of 128 + 64 / 128; theta 1e6) forward and
    backward at T 16,384 in bf16, through Mosaic for the chip: a K grid
    32 tiles deep in both two-width kernels; the forward's
    statistic leaves at 8 lanes a head — (1, 16, 16384, 16) f32, not a
    128-lane block a head, which at this length was 256 MiB a layer
    held from the forward to the backward and kept a five-block step
    off the chip — and dq / dk / dv leave their kernel in bf16 (PR
    52).  The backward is ONE call, ``znicz_flash_bwd_mla`` (PR 53): a
    pair's whole dq, 24 MiB of f32, stays in VMEM, and what the call
    asks for — 40 MiB — compiles through Mosaic for this chip."""
    import jax
    import jax.numpy as jnp

    from znicz_tpu.dummy import DummyUnit, DummyWorkflow
    from znicz_tpu.memory import Vector
    from znicz_tpu.ops import attention, pallas_kernels
    b, t, d = 1, 16384, 2048
    monkeypatch.setattr(pallas_kernels, "is_tpu_device",
                        lambda device: True)
    root.common.precision_type = "bfloat16"
    wf = DummyWorkflow()
    src = DummyUnit(wf, output=Vector(np.zeros((b, t, d), np.float32),
                                      name="x"))
    unit = attention.MultiHeadAttention(
        wf, n_heads=32, causal=True, include_bias=False, pre_norm="rms",
        residual=True, kv_latent=512, qk_nope=128, qk_rope=64,
        v_head_dim=128, norm_eps=1e-6, rope={"theta": 1000000})
    unit.link_attrs(src, ("input", "output"))
    unit.initialize(device=XLADevice())
    assert unit._flash.runs and unit._flash.tile == 512
    assert unit._flash.backward_passes == 1
    assert unit.weights.shape == (d, 32 * 192 + 512 + 64)
    assert not unit.weights_q_up

    def struct(a):
        return None if a is None else jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=v5e_chip)

    def step(dy, *args):
        out, pullback = jax.vjp(unit.xla_forward, *args)
        return out, pullback(dy)

    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        text = jax.jit(step).lower(
            jax.ShapeDtypeStruct((b, t, d), jnp.float32,
                                 sharding=v5e_chip),
            *(struct(a) for a in unit.forward_args())) \
            .compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
    calls = {}
    for kernel in ("znicz_flash_fwd_mla", "znicz_flash_bwd_mla"):
        found = re.search(rf"%\w*{kernel}[._\d]* = (\([^=]*\)|\S+) "
                          rf"custom-call\(.*", text)
        assert found, kernel
        calls[kernel] = found.group(1)
        if kernel == "znicz_flash_bwd_mla":
            whole_call = found.group(0)
    assert "znicz_flash_bwd_mla_d" not in text
    assert "f32[1,16,16384,16]" in calls["znicz_flash_fwd_mla"]
    for kernel, result in calls.items():
        # no (T, H·128) array leaves a kernel in f32
        assert "f32[1,16384,4096]" not in result, (kernel, result)
    # dk_nope, dv, dq_nope; dq_rope at 64 a head; dk_r per pair in f32
    backward = calls["znicz_flash_bwd_mla"]
    assert backward.count("bf16[1,16384,4096]") == 3
    assert backward.count("bf16[1,16384,2048]") == 1
    assert backward.count("f32[1,16,16384,128]") == 1
    assert backward.count("[") == 5
    from znicz_tpu.ops import pallas_mla
    ask = pallas_mla._resident_dq_bytes(t) + pallas_mla._STEP_VMEM
    assert ask == 40 * 2 ** 20
    # the call's scoped VMEM: what it asked for, and what Mosaic used
    # of it (a pair's whole dq and a grid step's tiles)
    asked, used = (int(re.search(
        rf'"{key}":\[\{{"memory_space":"1","offset":"0","size":"(\d+)"',
        whole_call).group(1)) for key in (
            "scoped_memory_configs", "used_scoped_memory_configs"))
    assert asked == ask
    assert pallas_mla._resident_dq_bytes(t) < used <= asked
