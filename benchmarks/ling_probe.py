"""The kernel families of the Ling-3.0-flash cell, alone at the cell's
shapes: the delta rule with a decay per key channel
(``znicz_kda_chunk_*`` / ``znicz_kda_state_*``, 32 heads of 128 × 128,
T 4,096), the two-width flash calls of the latent attention
(``znicz_flash_*_mla``, 32 heads, keys 128 + 64 shared, values 128;
both PR 37), and what lies between the q ‖ k ‖ v projection and the
rule (``znicz_qkv_prep_fwd`` / ``_bwd``, PR 40: 4 taps, SiLU, L2 norms
over (4,096, 12,288) f32, against their bytes and against the plain
``jax.numpy`` form under autodiff).

    chiprun -- python3 benchmarks/ling_probe.py          # check + time
    chiprun -- python3 benchmarks/ling_probe.py --only prep [--rows 1024 2048 4096]
    python3 benchmarks/ling_probe.py --compile-only      # here: the
        real Mosaic / XLA-TPU compile for a described v5e, no chip
        (one of the rule's chunk kernels alone, for a dump of its
        schedule: benchmarks/delta_chunk_probe.py --compile-only
        --kernel kda_chunk_bwd)
    python3 benchmarks/ling_probe.py --compile-step [--t 8192]   # here:
        the CELL's whole step program compiled for a described v5e
        (≈ 4 minutes: 885 M parameters drawn on the host) — the
        compiler's memory analysis, or its refusal; a compile is not a
        run (``--workload lfm2_train_1of2``: another LM cell's step)

On the chip each family is checked in f32 against its ``jax.numpy``
form at a length that form can hold, then timed in bf16 forward and
forward + backward at T 4,096.  One JSON line per arm.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax                                                  # noqa: E402
import jax.numpy as jnp                                     # noqa: E402

from znicz_tpu.ops import delta_net                         # noqa: E402
from znicz_tpu.ops import pallas_delta as pd                # noqa: E402
from znicz_tpu.ops import pallas_mla                        # noqa: E402
from znicz_tpu.ops.moe import _silu                         # noqa: E402

H, DK, DV, ROPE, TAPS, EPS = 32, 128, 128, 64, 4, 1e-6
BF16 = jnp.dtype(jnp.bfloat16)


def emit(**line) -> None:
    print(json.dumps(line), flush=True)


def delta_inputs(t: int, heads: int = H):
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    q = jax.random.normal(keys[0], (1, t, heads, DK), jnp.float32)
    k = jax.random.normal(keys[1], (1, t, heads, DK), jnp.float32)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * DK ** -0.5
    v = jax.random.normal(keys[2], (1, t, heads, DV), jnp.float32)
    log_alpha = -5.0 * jax.random.uniform(keys[3], (1, t, heads, DK))
    beta = jax.random.uniform(keys[4], (1, t, heads))
    weight = jax.random.normal(keys[5], (1, t, heads, DV), jnp.float32)
    return (q, k, v, log_alpha, beta), weight


def prep_inputs(t: int, heads: int = H):
    keys = jax.random.split(jax.random.PRNGKey(2), 5)
    wide = heads * (2 * DK + DV)
    rows = (jax.random.normal(keys[0], (1, t, wide), jnp.float32),
            0.5 * jax.random.normal(keys[1], (wide, TAPS), jnp.float32))
    return rows, tuple(
        jax.random.normal(key, (1, heads, t, d), jnp.float32)
        for key, d in zip(keys[2:], (DK, DK, DV)))


def prep_plain(u, taps):
    """The unit's plain form (``GatedDeltaNet._heads`` of the SiLU of
    ``causal_conv``), then the move to head-major."""
    unit = types.SimpleNamespace(
        n_heads=u.shape[-1] // (2 * DK + DV), key_dim=DK, value_dim=DV,
        norm_eps=EPS)
    return tuple(jnp.moveaxis(a, 2, 1)
                 for a in delta_net.GatedDeltaNet._heads(
                     unit, jnp, _silu(jnp, delta_net.causal_conv(
                         jnp, u, taps))))


def prep_kernels(rows=None):
    return lambda u, taps: pd.qkv_prep(
        u, taps, u.shape[-1] // (2 * DK + DV), DK, DV, EPS, rows=rows)


def prep_programs(rule):
    def both(u, taps, weights):
        return jax.grad(lambda *a: sum(
            jnp.sum(out * w) for out, w in zip(rule(*a), weights)),
            (0, 1))(u, taps)
    return jax.jit(rule), jax.jit(both)


def prep_arm(t: int, row_tiles) -> None:
    """``znicz_qkv_prep_*`` checked in f32 against the plain form, then
    timed against their bytes: the forward reads u and writes q, k, v
    (2 arrays of u's size), the backward reads u and three cotangents
    and writes du (3)."""
    rows, weights = prep_inputs(512, 4)
    want_f, want_b = prep_programs(prep_plain)
    got_f, got_b = prep_programs(prep_kernels(128))      # four row tiles
    emit(family="prep", check_t=512, f32_forward_against_plain=worst(
        got_f(*rows), want_f(*rows)),
        f32_backward_against_plain=worst(got_b(*rows, weights),
                                         want_b(*rows, weights)))
    rows, weights = prep_inputs(t)
    one = rows[0].size * 4 / 1e9                  # GB an array of u's
    plain_f, plain_b = prep_programs(prep_plain)
    emit(family="prep", t=t, form="jax.numpy", gb_an_array=one,
         forward_ms=timed(plain_f, *rows),
         backward_ms=timed(plain_b, *rows, weights))
    for tile in row_tiles:
        forward, backward = prep_programs(prep_kernels(tile))
        f_ms, b_ms = timed(forward, *rows), timed(backward, *rows,
                                                  weights)
        emit(family="prep", t=t, form="kernels", rows=tile,
             forward_ms=f_ms, backward_ms=b_ms,
             forward_gb_s=2 * one / f_ms * 1e3,
             backward_gb_s=3 * one / b_ms * 1e3)


def mla_inputs(t: int, dtype, heads: int = H):
    keys = jax.random.split(jax.random.PRNGKey(1), 6)

    def draw(key, width):
        return (0.3 * jax.random.normal(key, (1, t, width),
                                        jnp.float32)).astype(dtype)
    rows = (draw(keys[0], heads * DK), draw(keys[1], heads * ROPE),
            draw(keys[2], heads * DK), draw(keys[3], ROPE),
            draw(keys[4], heads * DV))
    return rows, draw(keys[5], heads * DV)


def mla_plain(qn, qr, kn, kr, v):
    b, t, _ = qn.shape
    h = qn.shape[-1] // DK
    q = jnp.concatenate([qn.reshape(b, t, h, DK),
                         qr.reshape(b, t, h, ROPE)], -1)
    k = jnp.concatenate([kn.reshape(b, t, h, DK), jnp.broadcast_to(
        kr[:, :, None, :], (b, t, h, ROPE))], -1)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest")
    seen = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    p = jax.nn.softmax(jnp.where(seen, s, -1e30), -1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.reshape(b, t, h, DV),
                      precision="highest").reshape(b, t, h * DV)


def programs(rule):
    forward = jax.jit(rule)

    def both(*args):
        *rows, weight = args
        return jax.value_and_grad(
            lambda *a: jnp.sum(rule(*a).astype(jnp.float32) * weight),
            tuple(range(len(rows))))(*rows)
    return forward, jax.jit(both)


def timed(fn, *args, runs: int = 5) -> float:
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(runs):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / runs * 1e3


def worst(got, want) -> float:
    return max(float(jnp.abs(g.astype(jnp.float32) - w).max()
                     / (jnp.abs(w).max() + 1e-30))
               for g, w in zip(jax.tree.leaves(got),
                               jax.tree.leaves(want)))


def kda(dot_dtype):
    return lambda *a: pd.gated_delta_rule(*a, kernel=True,
                                          dot_dtype=dot_dtype)


def compile_step(t: int, workload: str = "ling_train_1of64",
                 text_to: str | None = None) -> int:
    """A cell's step program (this cell's, or another LM cell's of the
    ``train_lm`` driver) at sequence length ``t``, compiled for a
    described v5e: what the compiler says it needs."""
    import numpy as np
    from benchmarks.ouro_probe import compile_for_described_chip
    from znbench.harness import discovery
    from znbench.harness.program import engine_options, layer_table
    from znicz_tpu.backends import XLADevice
    from znicz_tpu.loader.fullbatch import ArrayLoader
    from znicz_tpu.models.standard_workflow import StandardWorkflow
    from znicz_tpu.observe import metrics as obs_metrics
    from znicz_tpu.utils import prng
    from znicz_tpu.utils.config import root

    cell = discovery.find_cell(workload)
    config, traffic = cell.config, cell.traffic
    root.common.precision_type = config["precision"]["precision_type"]
    prng.seed_all(0)
    steps = int(traffic["steps_per_epoch"])
    ids = np.random.default_rng(0).integers(
        0, config["input"]["vocab"], size=(steps, t + 1))
    options = dict(config["precision"].get("engine", {}),
                   **traffic.get("engine", {}))
    # the kernels' set-up without a chip; Mosaic compiles them below
    options.update(pallas_interpret=True, flash_attention=True,
                   moe_grouped_matmul=True, delta_scan_kernel=True)
    with engine_options(options):
        wf = StandardWorkflow(
            name=config["workflow"]["name"],
            loader_factory=lambda w: ArrayLoader(
                w, train_data=ids[:, :-1].astype(np.float32),
                train_labels=ids[:, 1:].astype(np.int32),
                minibatch_size=1),
            layers=layer_table(config), decision_config={"max_epochs": 1})
        wf._max_fires = 10 ** 9
        wf.initialize(device=XLADevice())
        for unit in wf.forwards:     # through Mosaic, not the interpreter
            for flag in ("_interpret", "_gmm_interpret"):
                if getattr(unit, flag, False):
                    setattr(unit, flag, False)
        line = {"workload": workload, "t": t}
        latent = obs_metrics.REGISTRY.get("znicz_attention_latent") or {}
        passes = sorted({int(gauge.value) for (_, stat), gauge
                         in latent.items() if stat == "backward_passes"})
        if passes:      # what the rule read from the shapes, per layer
            line["mla_backward_passes"] = passes
        flash = obs_metrics.REGISTRY.get("znicz_flash_backward") or {}
        if flash:       # the one-width kernels' rule, the same way
            line["flash_backward_passes"] = sorted(
                {int(gauge.value) for (_, stat), gauge in flash.items()
                 if stat == "passes"})
            line["flash_resident_dq_mib"] = sorted(
                {gauge.value / 2 ** 20 for (_, stat), gauge
                 in flash.items() if stat == "resident_dq_bytes"})
        try:
            line.update(compile_for_described_chip(wf, text_to),
                        loads=True)
        except Exception as exc:  # noqa: BLE001 — the refusal is the result
            said = str(exc)
            at = said.find("RESOURCE_EXHAUSTED")
            line.update(loads=False, refusal=(
                said[at:at + 300] if at >= 0
                else f"{type(exc).__name__}: {said[:300]}"))
    emit(**line)
    return 0 if line["loads"] else 1


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--compile-only", action="store_true")
    parser.add_argument("--compile-step", action="store_true")
    parser.add_argument("--t", type=int, default=4096)
    parser.add_argument("--workload", default="ling_train_1of64",
                        help="the cell whose step --compile-step compiles")
    parser.add_argument("--text", metavar="FILE",
                        help="where --compile-step writes the compiled "
                             "program's text")
    parser.add_argument("--only", choices=("kda", "mla", "prep"),
                        nargs="+", default=("kda", "mla", "prep"))
    parser.add_argument("--rows", type=int, nargs="+",
                        default=[pd.PREP_ROWS],
                        help="row tiles of the prep kernels to time")
    args = parser.parse_args()
    if args.compile_step:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        return compile_step(args.t, args.workload, args.text)
    if args.compile_only:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        jax.config.update("jax_enable_compilation_cache", False)
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        one = SingleDeviceSharding(topo.devices[0])

        def struct(a):
            return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)
        with jax.default_device(jax.devices("cpu")[0]):
            rows, weight = jax.eval_shape(lambda: delta_inputs(args.t))
            mla_rows, mla_weight = jax.eval_shape(
                lambda: mla_inputs(args.t, BF16))
            prep_rows, prep_weights = jax.eval_shape(
                lambda: prep_inputs(args.t))
        for name, (forward, both), shaped in (
                ("kda", programs(kda(BF16)), (rows, weight)),
                ("mla", programs(pallas_mla.latent_flash_attention),
                 (mla_rows, mla_weight)),
                ("prep", prep_programs(prep_kernels()),
                 (prep_rows, prep_weights))):
            if name not in args.only:
                continue
            rows_, weight_ = jax.tree.map(struct, shaped)
            t0 = time.perf_counter()
            forward.lower(*rows_).compile()
            compiled = both.lower(*rows_, weight_).compile()
            emit(family=name, t=args.t, compiled_s=time.perf_counter() - t0,
                 temp_gb=compiled.memory_analysis().temp_size_in_bytes
                 / 1e9)
        return 0

    if "kda" in args.only:
        # f32 against jax.numpy, at a length the plain form can hold
        small, heads = 512, 4
        rows, weight = delta_inputs(small, heads)
        with jax.default_matmul_precision("highest"):
            want = programs(lambda *a: pd.gated_delta_rule(*a))[1](
                *rows, weight)
        got = programs(kda(None))[1](*rows, weight)
        emit(family="kda", check_t=small, f32_against_jax_numpy=worst(
            got, want))
        # bf16, the cell's shapes
        rows, weight = delta_inputs(args.t)
        forward, both = programs(kda(BF16))
        emit(family="kda", t=args.t, forward_ms=timed(forward, *rows),
             forward_backward_ms=timed(both, *rows, weight))
    if "mla" in args.only:
        rows, weight = mla_inputs(1024, jnp.float32, 4)
        with jax.default_matmul_precision("highest"):
            want = programs(mla_plain)[1](*rows, weight)
            got = programs(pallas_mla.latent_flash_attention)[1](
                *rows, weight)
        emit(family="mla", check_t=1024, f32_against_plain=worst(
            got, want))
        rows, weight = mla_inputs(args.t, BF16)
        forward, both = programs(pallas_mla.latent_flash_attention)
        emit(family="mla", t=args.t, forward_ms=timed(forward, *rows),
             forward_backward_ms=timed(both, *rows, weight))
    if "prep" in args.only:
        prep_arm(args.t, args.rows)
    return 0


if __name__ == "__main__":
    sys.exit(main())
