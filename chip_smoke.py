#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

One process drives the main path once through the entry points a user
calls, at the real widths of the models the repo claims (depth is one
block, weights are random from a seed):

- **train**: the AlexNet sample (227×227×3, 1000 classes, bf16, batch
  768) through ``JitRegion.run_chunk(16)`` — the driver
  ``StandardWorkflow.run_chunked`` and ``bench.py`` use — then
  ``export_forward``;
- **kernels**: the sequence stack (attention → layer_norm → softmax) at
  T=2048, batch 16, bf16, 8 heads of 64 (non-causal, causal) and of
  128 (causal): a few TRAINING steps, so the flash forward, both forms
  of its backward (dq + dk/dv non-causal, the one pass causal) and both
  layer-norm kernels go through Mosaic, and one forward compared with
  the XLA cores on the same device;
- **serve**: the exported AlexNet behind ``ServingEngine`` (ragged
  requests of 1, 3, 8 rows, checked against the numpy oracle), then an
  LM (embedding → pos_encoding → causal attention → last_token →
  softmax; D=512, 8 heads, vocabulary 32768) trained a few steps,
  exported with ``attach_decode_meta`` and served through the paged
  ``DecodeEngine``: two waves of greedy generations checked token by
  token against a numpy full-forward oracle;
- **mesh** (only when more than one device is visible): train and
  kernels again on a data-parallel mesh over every local chip (ZeRO-1,
  kernels per shard under shard_map), and the sequence stack with ring
  attention on the launcher's standalone ``n_model=2`` mesh.

Every stage prints one line with the platform, device kind and count,
set-up (compile) seconds and run seconds.  The exit code is non-zero if
JAX finds no TPU, if any stage raised, if a kernel gate did not engage
or ran interpreted, if any request or lane failed, or if any program
was built after warm-up.  The last line of a passing run is
``{"ok": true, "device": {...}}`` with the device as JAX reports it.

    python chip_smoke.py [stage ...]      # on the chip; default: all
    python chip_smoke.py --cpu-toy        # the same stages at toy sizes
                                          # on the CPU, kernels
                                          # interpreted, every line
                                          # labelled platform=cpu

It spawns no process, starts no profiler and writes only under
``.chip_smoke/`` beside this file and JAX's compilation cache
(``JAX_COMPILATION_CACHE_DIR`` where set, else ``.jax_cache/``).
"""

from __future__ import annotations

import collections
import dataclasses
import gc
import json
import os
import shutil
import sys
import time
import traceback

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)
OUT_DIR = os.path.join(REPO, ".chip_smoke")

#: the widths the repo claims; a width is never cut, only depth
REAL = {
    "alexnet": {"image": 227, "classes": 1000, "batch": 768, "chunk": 16},
    # (dim, heads, causal): head sizes 64, 64 and 128
    "seq": {"batch": 16, "t": 2048,
            "configs": ((512, 8, False), (512, 8, True), (1024, 8, True))},
    "lm": {"vocab": 32768, "dim": 512, "heads": 8, "train_t": 64,
           "batch": 32, "max_t": 64, "max_prompt": 32, "align": 16,
           "page": 16, "new": 16},
}
#: what the CPU and the Pallas interpreter finish in seconds
TOY = {
    "alexnet": {"image": 67, "classes": 10, "batch": 8, "chunk": 2},
    "seq": {"batch": 4, "t": 32,
            "configs": ((16, 2, False), (16, 2, True), (32, 2, True))},
    "lm": {"vocab": 512, "dim": 16, "heads": 2, "train_t": 16,
           "batch": 32, "max_t": 32, "max_prompt": 16, "align": 8,
           "page": 8, "new": 8},
}

#: jax.monitoring events: every program jax builds or loads from its
#: persistent cache ends one backend-compile span; a load also counts
#: one cache hit
_PROGRAM_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_events: collections.Counter = collections.Counter()
_listening = False


class SmokeFailure(Exception):
    """A check of this script did not hold."""


def check(cond, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


@dataclasses.dataclass
class Ctx:
    toy: bool
    devices: list
    sizes: dict
    #: bundles handed from one stage to the next
    bundles: dict = dataclasses.field(default_factory=dict)

    def device(self, mesh=None):
        """The backend object every stage initializes on: the TPU
        (``TPUDevice`` raises where there is none), or under
        ``--cpu-toy`` whatever platform was pinned."""
        from znicz_tpu.backends import TPUDevice, XLADevice
        cls = XLADevice if self.toy else TPUDevice
        return cls(mesh=mesh)


def listen() -> None:
    """Count program builds and cache hits from ``jax.monitoring``
    (listeners cannot be removed, so one pair per process)."""
    global _listening
    if _listening:
        return
    import jax
    jax.monitoring.register_event_listener(
        lambda event, **kw: _events.update([event]))
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _secs, **kw: _events.update([event]))
    _listening = True


def programs() -> int:
    """XLA programs this process has built or loaded so far."""
    return _events[_PROGRAM_EVENT]


def znicz_compiles() -> float:
    """The repo's own count: ``znicz_xla_compiles_total`` over every
    site."""
    from znicz_tpu.observe import metrics
    family = metrics.REGISTRY.get("znicz_xla_compiles_total")
    if family is None:
        return 0.0
    return sum(float(child.value) for _key, child in family.items())


def host(vec) -> np.ndarray:
    """A Vector's device value as float32 on the host (also a fence:
    the read waits for the program that produces it)."""
    vec.map_read()
    return np.asarray(vec.mem).astype(np.float32)


def train_loss_sum(wf) -> float:
    """Cross-entropy summed over every TRAIN sample so far (the
    evaluator accumulates it on the device)."""
    from znicz_tpu.loader.base import TRAIN
    return float(host(wf.evaluator.epoch_loss)[TRAIN])


def set_kernel_gates(ctx: Ctx, on: bool) -> None:
    """The flash-attention and layer-norm gates for the next
    ``initialize``: their defaults on the chip (auto = engaged on a
    TPU), interpreted under ``--cpu-toy``, or forced to the XLA cores
    for the reference forward."""
    from znicz_tpu.utils.config import root
    engine = root.common.engine
    if not on:
        engine.flash_attention = engine.pallas_layer_norm = False
    elif ctx.toy:
        engine.flash_attention = engine.pallas_layer_norm = True
        engine.pallas_interpret = True
    else:
        engine.flash_attention = engine.pallas_layer_norm = "auto"


# ----------------------------------------------------------------------
# mesh checks
# ----------------------------------------------------------------------
def check_spread(ctx: Ctx, wf, what: str) -> dict:
    """On a mesh: the batch, the output and the parameters or the
    optimizer state each live on every device, some optimizer state is
    really split (ZeRO-1), and every chip holds memory."""
    n = len(ctx.devices)
    groups = {
        "batch": [wf.loader.minibatch_data],
        "output": [wf.forwards[-1].output],
        "params_or_opt": [v for gd in wf.gds for v in (
            gd.weights, gd.accumulated_gradient_weights) if v],
    }
    split = 0
    for name, vecs in groups.items():
        on = set()
        for vec in vecs:
            sharding = vec.devmem.sharding
            on |= set(sharding.device_set)
            if name == "params_or_opt" \
                    and not sharding.is_fully_replicated:
                split += 1
        check(len(on) == n,
              f"{what}: {name} lives on {len(on)} of {n} devices")
    check(split > 0, f"{what}: no parameter or optimizer state is "
                     f"split over the mesh (ZeRO-1 did not engage)")
    held = []
    for dev in ctx.devices:
        stats = dev.memory_stats()
        if stats is None:        # the CPU backend reports none
            continue
        held.append(int(stats["bytes_in_use"]))
        check(held[-1] > 0, f"{what}: {dev} holds no memory")
    return {"sharded_state_vectors": split,
            "min_bytes_in_use": min(held) if held else "n/a"}


# ----------------------------------------------------------------------
# train: AlexNet through run_chunk
# ----------------------------------------------------------------------
def stage_train(ctx: Ctx, mesh=None) -> dict:
    from znicz_tpu.models.samples import alexnet
    from znicz_tpu.utils import prng
    from znicz_tpu.utils.config import root

    size = ctx.sizes["alexnet"]
    batch, chunk = size["batch"], size["chunk"]
    root.common.precision_type = "bfloat16"
    prng.seed_all(1234)
    t0 = time.perf_counter()
    # one epoch = one chunk, so a scanned chunk never spans the
    # epoch-boundary reshuffle (the run_chunked contract)
    wf = alexnet.build(
        minibatch_size=batch, image_size=size["image"],
        n_classes=size["classes"], n_train_samples=chunk * batch,
        n_valid_samples=0, max_epochs=10 ** 6)
    wf.initialize(device=ctx.device(mesh))
    region = wf._region_unit.region

    def dispatch() -> float:
        for _ in range(chunk):
            wf.loader.run()          # host bookkeeping only
        region.run_chunk(chunk)
        return train_loss_sum(wf)    # the read is the fence

    # two warm-up dispatches: on a mesh the compiler assigns the first
    # step's outputs their shardings, and the second fire may
    # specialize on them once
    sums = [0.0, dispatch(), dispatch()]
    setup_s = time.perf_counter() - t0
    built, counted = programs(), znicz_compiles()
    t1 = time.perf_counter()
    sums += [dispatch(), dispatch()]
    run_s = time.perf_counter() - t1
    built, counted = programs() - built, znicz_compiles() - counted
    losses = [(b - a) / (chunk * batch) for a, b in zip(sums, sums[1:])]
    check(all(np.isfinite(losses)), f"train loss not finite: {losses}")
    check(len(set(losses)) > 1, f"train loss does not move: {losses}")
    check(np.isfinite(host(wf.forwards[-1].weights)).all(),
          "non-finite weights after training")
    check(built == 0 and counted == 0,
          f"{built} programs built ({counted:g} counted by "
          f"znicz_xla_compiles_total) after warm-up")
    info = {"setup_s": round(setup_s, 1), "run_s": round(run_s, 2),
            "steps": 4 * chunk, "batch": batch,
            "loss": "→".join(f"{v:.4f}" for v in losses),
            "programs_after_warmup": built}
    if mesh is not None:
        info.update(check_spread(ctx, wf, "train"))
    else:
        t2 = time.perf_counter()
        path = os.path.join(OUT_DIR, "alexnet.npz")
        wf.export_forward(path)
        ctx.bundles["alexnet"] = path
        info["export_s"] = round(time.perf_counter() - t2, 1)
    return info


# ----------------------------------------------------------------------
# kernels: the sequence stack, trained, against the XLA cores
# ----------------------------------------------------------------------
def seq_workflow(ctx: Ctx, dim: int, heads: int, causal: bool,
                 device, seq_parallel: bool = False):
    """attention → layer_norm → softmax over (batch, T, dim) bf16
    inputs — the ``benchmarks/seq_bench.py`` stack."""
    import ml_dtypes

    from znicz_tpu.loader.fullbatch import ArrayLoader
    from znicz_tpu.models.standard_workflow import StandardWorkflow
    from znicz_tpu.utils import prng

    size = ctx.sizes["seq"]
    n = 4 * size["batch"]
    rng = np.random.default_rng(3)
    x = rng.standard_normal((n, size["t"], dim), dtype=np.float32)
    x = (0.3 * x).astype(ml_dtypes.bfloat16)
    y = rng.integers(0, 8, size=n).astype(np.int32)
    gd = {"learning_rate": 0.01, "gradient_moment": 0.9}
    prng.seed_all(11)
    wf = StandardWorkflow(
        name="smoke_seq",
        loader_factory=lambda w: ArrayLoader(
            w, train_data=x, train_labels=y,
            minibatch_size=size["batch"]),
        layers=[
            {"type": "attention",
             "->": {"n_heads": heads, "causal": causal,
                    "seq_parallel": seq_parallel}, "<-": gd},
            {"type": "layer_norm", "->": {}, "<-": gd},
            {"type": "softmax", "->": {"output_sample_shape": 8},
             "<-": gd},
        ],
        decision_config={"max_epochs": 10 ** 6})
    wf._max_fires = 10 ** 9
    wf.initialize(device=device)
    return wf


def first_step(wf) -> tuple[np.ndarray, np.ndarray]:
    """One training step; the attention and layer-norm outputs of its
    forward, on the host."""
    wf.loader.run()
    wf._region_unit.run()
    return host(wf.forwards[0].output), host(wf.forwards[1].output)


def layer_norm_np(x: np.ndarray, eps: float) -> np.ndarray:
    mu = x.mean(axis=-1, keepdims=True)
    var = ((x - mu) ** 2).mean(axis=-1, keepdims=True)
    return (x - mu) / np.sqrt(var + eps)


def kernel_run(ctx: Ctx, tag: str, wf, ring: bool
               ) -> tuple[np.ndarray, np.ndarray]:
    """Check the gates of an initialized stack, train it a few steps,
    and return the attention output and the layer-norm error of its
    first forward."""
    attn, ln = wf.forwards[0], wf.forwards[1]
    on_mesh = wf.device.mesh is not None
    if ring:
        check(attn.ring_active and attn._ring_fold == "pallas",
              f"{tag}: ring did not fold through the kernel "
              f"(active={attn.ring_active}, fold={attn._ring_fold})")
    else:
        check(attn._flash.runs, f"{tag}: flash gate did not engage")
        check((attn._flash.mesh is not None) == on_mesh,
              f"{tag}: flash kernel not per shard under shard_map")
    check(ln._pallas_ln, f"{tag}: layer-norm gate did not engage")
    check((ln._ln_mesh is not None) == on_mesh,
          f"{tag}: layer-norm kernel not per shard under shard_map")
    check(attn._flash.interpret == ctx.toy
          and ln._ln_interpret == ctx.toy,
          f"{tag}: kernels interpreted={attn._flash.interpret} on "
          f"platform {wf.device.jax_device.platform}")
    attn_out, ln_out = first_step(wf)
    sums = [0.0, train_loss_sum(wf)]
    built = programs()
    for _ in range(3):               # the per-step driver of wf.run()
        wf.loader.run()
        wf._region_unit.run()
        sums.append(train_loss_sum(wf))
    built = programs() - built
    losses = [(b - a) / ctx.sizes["seq"]["batch"]
              for a, b in zip(sums, sums[1:])]
    check(all(np.isfinite(losses)), f"{tag}: loss not finite {losses}")
    check(len(set(losses)) > 1, f"{tag}: loss does not move {losses}")
    check(built == 0, f"{tag}: {built} programs built after warm-up")
    if on_mesh:
        check_spread(ctx, wf, tag)
    # the layer-norm kernel against numpy on its own input (γ=1, β=0
    # at the first forward)
    ln_err = float(np.abs(
        ln_out - layer_norm_np(attn_out, ln.eps)).max())
    return attn_out, ln_err


def reference_run(tag: str, wf) -> np.ndarray:
    """The attention output of the first forward on the XLA cores."""
    attn, ln = wf.forwards[0], wf.forwards[1]
    check(not attn._flash.runs and not attn.ring_active
          and not ln._pallas_ln,
          f"{tag}: the reference forward did not take the XLA cores")
    return first_step(wf)[0]


def seq_case(ctx: Ctx, dim: int, heads: int, causal: bool,
             make_device, ring: bool = False) -> str:
    """One configuration: train it with the kernels engaged, compare
    its first forward with the XLA cores.  Returns a short verdict."""
    from znicz_tpu.utils.config import root
    root.common.precision_type = "bfloat16"
    tag = f"dh{dim // heads}{'c' if causal else 'n'}" \
        + ("-ring" if ring else "")
    set_kernel_gates(ctx, on=True)
    attn_out, ln_err = kernel_run(ctx, tag, seq_workflow(
        ctx, dim, heads, causal, make_device(), seq_parallel=ring),
        ring)
    gc.collect()                     # free the stack before the next
    set_kernel_gates(ctx, on=False)
    try:
        ref_out = reference_run(tag, seq_workflow(
            ctx, dim, heads, causal, make_device()))
    finally:
        set_kernel_gates(ctx, on=True)
    gc.collect()
    attn_err = float(np.abs(attn_out - ref_out).max()
                     / (np.abs(ref_out).max() + 1e-6))
    # bf16 storage rounds at 2**-8 of the value; the two cores also
    # sum in different orders
    check(attn_err <= 3e-2, f"{tag}: attention differs from the XLA "
                            f"core by {attn_err:.3g} of its range")
    check(ln_err <= 5e-2, f"{tag}: layer norm differs from numpy by "
                          f"{ln_err:.3g}")
    return f"{tag}:attn_err={attn_err:.1e},ln_err={ln_err:.1e}"


def stage_kernels(ctx: Ctx, make_device=None, configs=None) -> dict:
    make_device = make_device or ctx.device
    t0 = time.perf_counter()
    verdicts = [seq_case(ctx, dim, heads, causal, make_device)
                for dim, heads, causal in
                (configs or ctx.sizes["seq"]["configs"])]
    # set-up and run interleave here (two programs per case); the
    # split is not worth a number
    return {"setup_s": round(time.perf_counter() - t0, 1),
            "run_s": "incl", "flash_pallas": True, "pallas_ln": True,
            "interpret": ctx.toy, "cases": "|".join(verdicts)}


# ----------------------------------------------------------------------
# serve: one-shot scorer, then paged decode
# ----------------------------------------------------------------------
def serve_alexnet(ctx: Ctx) -> dict:
    from znicz_tpu.backends import NumpyDevice
    from znicz_tpu.export import ExportedModel
    from znicz_tpu.serving import ServingEngine

    if "alexnet" not in ctx.bundles:
        stage_train(ctx)             # selected alone: train it first
    path = ctx.bundles["alexnet"]
    size = ctx.sizes["alexnet"]
    shape = (size["image"], size["image"], 3)
    rng = np.random.default_rng(5)
    t0 = time.perf_counter()
    with ServingEngine(path, max_batch=8, max_delay_ms=2.0,
                       device=ctx.device()) as eng:
        setup_s = time.perf_counter() - t0
        replies = {}

        def wave() -> None:
            for n in (1, 3, 8):      # partial, odd, full bucket
                x = rng.uniform(-1, 1, (n,) + shape).astype(np.float32)
                out = np.asarray(eng(x, timeout=300), np.float32)
                check(out.shape == (n, size["classes"]),
                      f"reply shape {out.shape} for {n} rows")
                check(np.isfinite(out).all(), "non-finite reply")
                replies[n] = (x, out)

        t1 = time.perf_counter()
        wave()
        built = programs()
        wave()                       # warmed: must build nothing
        run_s = time.perf_counter() - t1
        built = programs() - built
        stats = eng.stats()
    res = stats["resilience"]
    check(stats["served"] == stats["submitted"] == 6
          and stats["rejected"] == 0 and res["retried"] == 0
          and res["expired"] == 0 and res["shed"] == 0,
          f"failed requests: {stats}")
    check(built == 0, f"{built} programs built by warmed requests")
    x, out = replies[3]
    ref = np.asarray(ExportedModel.load(path, device=NumpyDevice())(x),
                     np.float32)
    err = float(np.abs(out - ref).max() / (np.abs(ref).max() + 1e-9))
    # the engine serves bf16, the oracle computes float32
    check(err <= 5e-2, f"AlexNet replies differ from the numpy oracle "
                       f"by {err:.3g} of the largest probability")
    return {"scorer_setup_s": round(setup_s, 1),
            "scorer_run_s": round(run_s, 2), "scorer_served": 6,
            "scorer_err": f"{err:.1e}",
            "scorer_programs": stats["programs_compiled"]}


def lm_workflow(ctx: Ctx):
    """The LM of ``benchmarks/serve_bench.py`` at real widths, on the
    next-token task ``x[t+1] = x[t] + 1 mod V``."""
    from znicz_tpu.loader.fullbatch import ArrayLoader
    from znicz_tpu.models.standard_workflow import StandardWorkflow
    from znicz_tpu.utils import prng
    from znicz_tpu.utils.config import root

    size = ctx.sizes["lm"]
    vocab, t = size["vocab"], size["train_t"]
    root.common.precision_type = "bfloat16"
    set_kernel_gates(ctx, on=True)
    # token ids ride the loader's float minibatch path, and bf16
    # storage holds integers exactly only up to 256: a real vocabulary
    # trains with bf16 matmuls over float32 activation storage
    root.common.engine.bf16_activations = False
    rng = np.random.default_rng(31)
    n = 4 * size["batch"]
    start = rng.integers(0, vocab, size=n)
    data = ((start[:, None] + np.arange(t)[None, :])
            % vocab).astype(np.float32)
    labels = ((start + t) % vocab).astype(np.int32)
    split = 3 * size["batch"]
    prng.seed_all(31)
    gd = {"learning_rate": 0.05, "gradient_moment": 0.9}
    wf = StandardWorkflow(
        name="smoke_lm",
        loader_factory=lambda w: ArrayLoader(
            w, train_data=data[:split], train_labels=labels[:split],
            valid_data=data[split:], valid_labels=labels[split:],
            minibatch_size=size["batch"]),
        layers=[
            {"type": "embedding",
             "->": {"vocab_size": vocab, "dim": size["dim"]}, "<-": gd},
            {"type": "pos_encoding", "->": {}},
            {"type": "attention",
             "->": {"n_heads": size["heads"], "causal": True},
             "<-": gd},
            {"type": "last_token", "->": {}},
            {"type": "softmax",
             "->": {"output_sample_shape": vocab}, "<-": gd},
        ],
        decision_config={"max_epochs": 1})
    wf._max_fires = 10 ** 6
    try:
        wf.initialize(device=ctx.device())
    finally:
        root.common.engine.bf16_activations = True   # the default
    return wf


def train_lm(ctx: Ctx) -> str:
    """Train the LM one epoch (a few steps) and export it with its
    paged-decode geometry."""
    from znicz_tpu.export import attach_decode_meta
    wf = lm_workflow(ctx)
    wf.run()
    loss = wf.decision.epoch_loss
    check(all(v is None or np.isfinite(v) for v in loss),
          f"LM loss not finite: {loss}")
    path = os.path.join(OUT_DIR, "lm.npz")
    wf.export_forward(path)
    attach_decode_meta(path, page_tokens=ctx.sizes["lm"]["page"])
    return path


def lm_oracle_logits(params: dict, heads: int,
                     seq: np.ndarray) -> np.ndarray:
    """Next-token logits after ``seq`` by a float32 numpy forward over
    the whole sequence — no cache, no pages, no buckets."""
    from znicz_tpu.ops.pos_encoding import sinusoid_table
    x = params["layer0_weights"][seq].astype(np.float32)
    t, d = x.shape
    dh = d // heads
    x = x + sinusoid_table(t, d)
    w, b = params["layer2_weights"], params["layer2_bias"]
    q = (x[-1] @ w[:, :d] + b[:d]).reshape(heads, dh)
    k = (x @ w[:, d:2 * d] + b[d:2 * d]).reshape(t, heads, dh)
    v = (x @ w[:, 2 * d:] + b[2 * d:]).reshape(t, heads, dh)
    s = np.einsum("hd,thd->ht", q, k) / np.sqrt(dh)
    p = np.exp(s - s.max(axis=-1, keepdims=True))
    p /= p.sum(axis=-1, keepdims=True)
    o = np.einsum("ht,thd->hd", p, v).reshape(d)
    y = o @ params["layer2_weights_out"] + params["layer2_bias_out"]
    return y @ params["layer4_weights"] + params["layer4_bias"]


def serve_lm(ctx: Ctx) -> dict:
    from znicz_tpu.export import read_bundle
    from znicz_tpu.observe import metrics
    from znicz_tpu.serving import DecodeEngine

    size = ctx.sizes["lm"]
    vocab, new = size["vocab"], size["new"]
    t0 = time.perf_counter()
    path = train_lm(ctx)
    train_s = time.perf_counter() - t0
    _manifest, params = read_bundle(path)
    params = {k: np.asarray(v, np.float32) for k, v in params.items()}
    rng = np.random.default_rng(8)
    system = rng.integers(0, vocab, size=size["align"]).astype(np.int32)

    def prompts(n: int) -> list[np.ndarray]:
        out = []
        for i in range(n):
            tail = rng.integers(0, vocab, size=int(rng.integers(
                1, size["max_prompt"] - len(system) + 1)))
            # prefix hits and misses, as a shared system prompt gives
            out.append((np.concatenate([system, tail]) if i % 2
                        else tail).astype(np.int32))
        return out

    t1 = time.perf_counter()
    eng = DecodeEngine(path, max_slots=4, max_t=size["max_t"],
                       max_prompt=size["max_prompt"],
                       prompt_align=size["align"],
                       max_new_tokens=new, device=ctx.device())
    eng.start()
    setup_s = time.perf_counter() - t1
    try:
        check(eng.model.paged, "the decode engine is not paged")

        def wave(n: int) -> list[tuple[np.ndarray, np.ndarray]]:
            asked = prompts(n)
            futures = [eng.submit(p) for p in asked]
            return [(p, np.asarray(f.result(timeout=600)))
                    for p, f in zip(asked, futures)]

        t2 = time.perf_counter()
        done = wave(6)
        built, counted = programs(), znicz_compiles()
        done += wave(6)              # warmed: must build nothing
        run_s = time.perf_counter() - t2
        built, counted = programs() - built, znicz_compiles() - counted
        stats = eng.stats()
        failed = metrics.trace_requests(eng._obs_id, "failed").value
    finally:
        eng.shutdown()
    res = stats["resilience"]
    check(stats["served"] == stats["submitted"] == 12
          and stats["rejected"] == 0 and failed == 0
          and res["retried"] == 0 and res["expired"] == 0
          and res["shed"] == 0,
          f"failed lanes or requests ({failed:g} failed): {stats}")
    check(built == 0 and counted == 0,
          f"{built} programs built ({counted:g} counted) by the "
          f"warmed wave")
    exact = total = 0
    worst = 0.0
    for prompt, tokens in done:
        check(len(tokens) == new and tokens.min() >= 0
              and tokens.max() < vocab,
              f"generated {tokens} for a prompt of {len(prompt)}")
        seq = prompt
        for token in tokens:
            logits = lm_oracle_logits(params, size["heads"], seq)
            # the engine's matmuls run at the device's default
            # precision: near-ties may flip, a wrong token may not
            # trail the oracle's best by more than a sliver of the
            # logit spread
            gap = float((logits.max() - logits[token])
                        / (logits.max() - np.median(logits) + 1e-9))
            worst = max(worst, gap)
            exact += int(token == int(np.argmax(logits)))
            total += 1
            seq = np.append(seq, token)
    check(worst <= 5e-2, f"a generated token trails the oracle's best "
                         f"by {worst:.3g} of the logit spread")
    return {"lm_train_s": round(train_s, 1),
            "decode_setup_s": round(setup_s, 1),
            "decode_run_s": round(run_s, 2),
            "decode_served": 12, "failed_lanes": int(failed),
            "tokens": total, "oracle_exact": f"{exact}/{total}",
            "oracle_gap": f"{worst:.1e}",
            "decode_programs": stats["programs_compiled"],
            "programs_after_warmup": built,
            "prefix_hits": stats["prefix_cache"]["hits"]}


def stage_serve(ctx: Ctx) -> dict:
    info = serve_alexnet(ctx)
    info.update(serve_lm(ctx))
    info = {"setup_s": round(info["scorer_setup_s"]
                             + info["decode_setup_s"], 1),
            "run_s": round(info["scorer_run_s"]
                           + info["decode_run_s"], 2), **info}
    return info


# ----------------------------------------------------------------------
# mesh: every local chip
# ----------------------------------------------------------------------
def stage_mesh(ctx: Ctx) -> dict:
    from znicz_tpu.launcher import Launcher
    from znicz_tpu.parallel import make_mesh

    n = len(ctx.devices)
    check(n > 1, "the mesh stage needs more than one device")
    configs = ctx.sizes["seq"]["configs"]

    def data_parallel():
        return ctx.device(make_mesh(devices=ctx.devices))

    def standalone_ring():
        # the CLI's standalone mode: --n-model 2 builds the local mesh
        return Launcher(backend="xla" if ctx.toy else "tpu",
                        n_model=2).make_device()

    t0 = time.perf_counter()
    train = stage_train(ctx, mesh=make_mesh(devices=ctx.devices))
    kernels = stage_kernels(ctx, make_device=data_parallel,
                            configs=(configs[0], configs[-1]))
    dim, heads, causal = configs[1]
    ring = seq_case(ctx, dim, heads, causal, standalone_ring,
                    ring=True)
    return {"setup_s": round(time.perf_counter() - t0, 1),
            "run_s": train["run_s"], "mesh_devices": n,
            "train_loss": train["loss"],
            "sharded_state_vectors": train["sharded_state_vectors"],
            "min_bytes_in_use": train["min_bytes_in_use"],
            "cases": kernels["cases"] + "|" + ring}


STAGES = {"train": stage_train, "kernels": stage_kernels,
          "serve": stage_serve, "mesh": stage_mesh}


def run_stage(name: str, ctx: Ctx) -> bool:
    """Run one stage, isolated: its failure is printed and counted and
    does not hide the next stage's."""
    dev = ctx.devices[0]
    try:
        info = STAGES[name](ctx)
        ok = True
    except Exception as exc:  # noqa: BLE001 — the boundary that keeps going
        traceback.print_exc()
        info = {"error": json.dumps(f"{type(exc).__name__}: {exc}"[:300])}
        ok = False
    fields = " ".join(f"{key}={value}" for key, value in info.items())
    print(f"stage={name} platform={dev.platform} "
          f"device_kind={json.dumps(dev.device_kind)} "
          f"devices={len(ctx.devices)} ok={ok} {fields}", flush=True)
    gc.collect()
    return ok


def cache_entries(directory: str) -> int:
    try:
        return len(os.listdir(directory))
    except FileNotFoundError:
        return 0


def main(argv: list[str] | None = None) -> int:
    args = list(sys.argv[1:] if argv is None else argv)
    toy = "--cpu-toy" in args
    names = [a for a in args if a != "--cpu-toy"]
    unknown = [a for a in names if a not in STAGES]
    if unknown:
        print(f"chip_smoke: unknown stage(s) {unknown}; expected "
              f"{list(STAGES)} or --cpu-toy", file=sys.stderr)
        return 2

    import logging

    import jax

    from znicz_tpu.backends import configure_compile_cache
    from znicz_tpu.utils.logger import setup_logging

    if toy:
        jax.config.update("jax_platforms", "cpu")
    cache_dir = configure_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not toy:
        # no result line: a CPU run must not read as a chip run
        print(f"chip_smoke: JAX found no TPU (platform="
              f"{dev.platform}).  --cpu-toy runs the same stages at "
              f"toy sizes on the CPU.", file=sys.stderr)
        return 2
    setup_logging(logging.INFO)
    listen()
    ctx = Ctx(toy=toy, devices=devices, sizes=TOY if toy else REAL)
    if not names:
        names = [n for n in STAGES if n != "mesh" or len(devices) > 1]
    shutil.rmtree(OUT_DIR, ignore_errors=True)
    os.makedirs(OUT_DIR)
    entries = cache_entries(cache_dir)
    print(f"chip_smoke: platform={dev.platform} "
          f"device_kind={json.dumps(dev.device_kind)} "
          f"devices={len(devices)} stages={','.join(names)} "
          f"compile_cache={cache_dir} entries_before={entries}",
          flush=True)
    t0 = time.perf_counter()
    try:
        failed = [name for name in names if not run_stage(name, ctx)]
    finally:
        shutil.rmtree(OUT_DIR, ignore_errors=True)   # bundles are big
    print(f"chip_smoke: platform={dev.platform} "
          f"seconds={time.perf_counter() - t0:.0f} "
          f"programs={programs()} cache_hits={_events[_CACHE_HIT_EVENT]} "
          f"compile_cache={cache_dir} entries_before={entries} "
          f"entries_after={cache_entries(cache_dir)} "
          f"failed={','.join(failed) or 'none'}", flush=True)
    print(json.dumps({
        "ok": not failed,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices)}}), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
