"""The one dispatch protocol of ``JitRegion`` (PR 27), held to the
same five properties under each of its four programs — ``run``
(``step``), ``run_chunk(4)``, ``run_accum(2)`` and ``run_undonated``
(``nodonate``):

(a) the first call compiles — one count on
    ``znicz_xla_compiles_total{site="region:<name>"}``, one
    ``compile:<region>`` span, no dispatch span — and the second call
    compiles nothing;
(b) the second call is exactly one cat-``region`` span, named and
    argued for the variant;
(c) ``znicz_region_steps_total`` grows by the variant's step count;
(d) the cached program is named ``program_name(<variant>)``;
(e) under a persisted store the variant tag handed to
    ``_persisted_program`` is the literal tuple the store keys on, and
    a fresh region over the same units loads the program without a
    compile.

The bodies themselves are guarded by the trajectory identities of
``test_device_schedule.py``, ``test_pipeline.py``,
``test_retrace_guard.py`` and ``test_zero1.py``.
"""

from __future__ import annotations

import pytest

from conftest import blob_classifier
from znicz_tpu.accelerated_units import JitRegion
from znicz_tpu.observe import metrics as obs_metrics
from znicz_tpu.observe import tracing as obs_tracing
from znicz_tpu.serving import aot_cache
from znicz_tpu.utils.config import root

#: variant → (the call, loader steps it consumes, the tag handed to the
#: store, the warmed call's span name and arguments, steps counted, the
#: program's name)
VARIANTS = {
    "step": (lambda r: r.run(), 1, ("step",), "dispatch", {}, "step"),
    "chunk4": (lambda r: r.run_chunk(4), 4, ("chunk", 4), "chunk",
               {"steps": 4}, "chunk4"),
    "accum2": (lambda r: r.run_accum(2), 2, ("accum", 2), "accum",
               {"micro": 2}, "accum2"),
    "nodonate": (lambda r: r.run_undonated(), 1, ("nodonate", None),
                 "dispatch", {}, "step"),
}
variants = pytest.mark.parametrize("variant", list(VARIANTS))


class Toy:
    """A two-layer classifier on the device schedule, its region driven
    by hand: ``call()`` advances the loader's host mirror by what the
    variant consumes, then dispatches."""

    def __init__(self, name: str, variant: str, store: str | bool = False):
        from znicz_tpu.backends import XLADevice

        # the accumulation buffers exist only where this says so at
        # initialize; off the store unless a test is about the store
        root.common.engine.grad_accum = 2
        root.common.engine.aot_cache = store
        self.wf = blob_classifier(name, n_per_class=64, epochs=10)
        self.wf.initialize(device=XLADevice())
        self.region = self.wf._region_unit.region
        (self._call, self.loads, self.tag, self.span, self.span_args,
         self.program) = VARIANTS[variant]

    def call(self, region: JitRegion | None = None) -> None:
        for _ in range(self.loads):
            self.wf.loader.run()
        self._call(region or self.region)

    def compiles(self) -> float:
        return obs_metrics.xla_compiles(f"region:{self.region.name}").value

    def steps(self) -> float:
        return obs_metrics.region_steps(self.region.name).value


def _spans(since: int) -> list[dict]:
    events = obs_tracing.TRACER.to_chrome_trace(since=since)["traceEvents"]
    # the sites' own spans: JAX's stamps (``jax:*``, children of a
    # ``compile:`` span) are tests/test_observe_setup.py's
    return [ev for ev in events if ev.get("ph") == "X"
            and ev.get("cat") in ("region", "compile")
            and not ev["name"].startswith("jax:")]


@variants
def test_first_call_compiles_once_second_compiles_nothing(variant):
    toy = Toy(f"dispatch_a_{variant}", variant)
    base = toy.compiles()
    mark = obs_tracing.TRACER.mark()
    toy.call()
    assert toy.compiles() == base + 1
    assert [(s["name"], s["cat"]) for s in _spans(mark)] \
        == [(f"compile:{toy.region.name}", "compile")]
    mark = obs_tracing.TRACER.mark()
    toy.call()
    assert toy.compiles() == base + 1
    assert [s for s in _spans(mark) if s["cat"] == "compile"] == []


@variants
def test_warm_call_is_one_region_span_named_for_the_variant(variant):
    toy = Toy(f"dispatch_b_{variant}", variant)
    toy.call()
    mark = obs_tracing.TRACER.mark()
    toy.call()
    (span,) = _spans(mark)
    assert (span["name"], span["cat"]) \
        == (f"{toy.span}:{toy.region.name}", "region")
    told = {k: v for k, v in span.get("args", {}).items()
            if k in ("steps", "micro", "chunk", "accum")}
    assert told == toy.span_args


@variants
def test_steps_counter_grows_by_the_variants_step_count(variant):
    toy = Toy(f"dispatch_c_{variant}", variant)
    base = toy.steps()
    toy.call()
    assert toy.steps() == base + toy.loads
    toy.call()
    assert toy.steps() == base + 2 * toy.loads


@variants
def test_cached_program_carries_the_variants_name(variant):
    toy = Toy(f"dispatch_d_{variant}", variant)
    toy.call()
    assert [fn.__name__ for fn in toy.region._cache.values()] \
        == [toy.region.program_name(toy.program)]
    assert toy.region.program_name(toy.program) \
        == f"znicz_{toy.program}__{toy.region.name}"


@variants
def test_store_is_keyed_on_the_variant_tag_and_reloads_it(
        variant, tmp_path, monkeypatch):
    monkeypatch.delenv("ZNICZ_AOT_CACHE", raising=False)
    aot_cache._caches.clear()
    try:
        toy = Toy(f"dispatch_e_{variant}", variant,
                  store=str(tmp_path / "store"))
        handed = []
        persisted = JitRegion._persisted_program

        def spy(self, variant, fn, leaves, donate):
            handed.append((variant, donate))
            return persisted(self, variant, fn, leaves, donate)

        monkeypatch.setattr(JitRegion, "_persisted_program", spy)
        toy.call()
        ((tag, donate),) = handed
        assert tag[:len(toy.tag)] == toy.tag
        assert donate == (variant != "nodonate")
        assert aot_cache.active_cache().entries(), "nothing was stored"
        # a region that has built nothing, over the same units
        fresh = JitRegion(toy.region.name, toy.region.units,
                          toy.region.device)
        base, steps = toy.compiles(), toy.steps()
        toy.call(fresh)
        assert toy.compiles() == base, "the stored program was rebuilt"
        assert toy.steps() == steps + toy.loads
        assert len(fresh._cache) == 1
    finally:
        aot_cache._caches.clear()


# ----------------------------------------------------------------------
# The leaves a step only writes stay in a donated program's signature
# and lend their buffers to their successors (an option since PR 37,
# every donated program since PR 47)
# ----------------------------------------------------------------------
def _trained(name: str, variant: str = "step", store: str | bool = False):
    """A toy after three hand-driven steps, its step program's lowered
    text donated and not, and its weights."""
    import numpy as np

    toy = Toy(name, variant, store=store)
    leaves = [vec._devmem for vec in toy.region._collect_vectors()]
    structs = [(leaf.shape, leaf.dtype) for leaf in leaves]
    for _ in range(3):
        toy.call()
    # a re-trace leaves tracers in the Vectors: they are put back
    held = [(vec, vec._devmem) for vec in toy.region._vectors]
    texts = {}
    try:
        for donate in (True, False):
            texts[donate] = JitRegion._jit(
                toy.region.build_callable(
                    tuple(bool(u.gate_skip) for u in toy.region.units)),
                donate, len(structs)).lower(*[
                    np.zeros(shape, dtype)
                    for shape, dtype in structs]).as_text()
    finally:
        for vec, leaf in held:
            vec._devmem = leaf
    weights = []
    for unit in toy.wf.forwards:
        unit.weights.map_read()
        weights.append(np.array(unit.weights.mem))
    return toy, len(structs), texts, weights


@pytest.mark.parametrize("donate", [False, True],
                         ids=["undonated", "donated"])
def test_a_leaf_the_step_only_writes_is_a_parameter_where_donated(donate):
    """Undonated, jit drops the write-only leaves from the program
    (nothing could take their buffers); donated, every leaf is a
    parameter and every one is aliased to an output — the old buffer
    IS the new one."""
    import re
    _, n_leaves, texts, _ = _trained(f"kept_{donate}")
    (signature,) = re.findall(r"func\.func public @main\((.*?)\) ->",
                              texts[donate], re.S)
    params = signature.count("%arg")
    aliased = signature.count("tf.aliasing_output")
    if donate:
        assert params == n_leaves and aliased == n_leaves
    else:
        assert aliased == 0 and params < n_leaves


def test_kept_leaves_train_the_same_weights():
    """The donated program, every leaf kept, and the undonated one,
    its write-only leaves dropped, train the same weights."""
    import numpy as np
    from znicz_tpu.utils import prng
    _, _, _, kept = _trained("kept_same_a", "step")
    prng.seed_all(1234)
    _, _, _, pruned = _trained("kept_same_b", "nodonate")
    for a, b in zip(pruned, kept):
        np.testing.assert_array_equal(a, b)


def test_the_store_keys_a_kept_program_apart(tmp_path, monkeypatch):
    """Same body, another signature: the donated program, which keeps
    every leaf, is not stored under the key a store of before PR 47
    holds the pruned one under."""
    monkeypatch.delenv("ZNICZ_AOT_CACHE", raising=False)
    aot_cache._caches.clear()
    keys = []
    real = aot_cache.jaxpr_key

    def spy(fn, leaves, extra=()):
        keys.append((real(fn, leaves, extra=extra),
                     real(fn, leaves, extra=tuple(
                         e for e in extra if e != "keep_written_leaves"))))
        return keys[-1][0]

    monkeypatch.setattr(aot_cache, "jaxpr_key", spy)
    try:
        _trained("kept_store", store=str(tmp_path / "store"))
        stored = {key for key, _ in aot_cache.active_cache().entries()}
        ((key, bare),) = set(keys)
        assert stored == {key} and key != bare
    finally:
        aot_cache._caches.clear()


@pytest.mark.parametrize("asked", [False, True])
def test_the_old_option_is_accepted_and_decides_nothing(asked):
    """``engine.keep_written_leaves`` (PR 37; two traffic mixes still
    set it) changes no program: a donated one keeps its leaves
    whatever it says."""
    root.common.engine.keep_written_leaves = asked
    try:
        _, n_leaves, texts, _ = _trained(f"kept_asked_{asked}")
        assert texts[True].count("tf.aliasing_output") == n_leaves
        assert texts[False].count("tf.aliasing_output") == 0
    finally:
        del root.common.engine.keep_written_leaves
