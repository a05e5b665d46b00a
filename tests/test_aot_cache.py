"""Persisted AOT executable cache (round 23 tentpole).

The store's safety model is the contract under test: a wrong program
can NEVER load (key mismatch or digest mismatch falls back silently to
tracing), a deserialized program is bitwise-interchangeable with a
freshly traced one, and every verdict is visible on the
``znicz_aot_cache_total`` series.  Wall-clock claims live in
``benchmarks/coldstart_bench.py``; this module pins semantics.
"""

from __future__ import annotations

import glob
import os

import numpy as np
import pytest

from conftest import make_blobs
from znicz_tpu.backends import XLADevice
from znicz_tpu.export import ExportedModel
from znicz_tpu.loader.fullbatch import ArrayLoader
from znicz_tpu.models.standard_workflow import StandardWorkflow
from znicz_tpu.observe import metrics as obs_metrics
from znicz_tpu.serving import aot_cache
from znicz_tpu.utils import prng
from znicz_tpu.utils.config import root


def _counter(family: str, **labels) -> float:
    fam = obs_metrics.REGISTRY.get(family)
    if fam is None:
        return 0.0
    want = tuple(str(labels[n]) for n in fam.labelnames)
    for key, child in fam.items():
        if key == want:
            return float(child.value)
    return 0.0


def _train_workflow(name: str, max_epochs: int = 1):
    data, labels = make_blobs(24, 3, 10)
    prng.seed_all(29)
    wf = StandardWorkflow(
        name=name,
        loader_factory=lambda w: ArrayLoader(
            w, train_data=data[:48], train_labels=labels[:48],
            valid_data=data[48:], valid_labels=labels[48:],
            minibatch_size=12),
        layers=[{"type": "all2all_tanh",
                 "->": {"output_sample_shape": 16},
                 "<-": {"learning_rate": 0.05,
                        "gradient_moment": 0.9}},
                {"type": "softmax", "->": {"output_sample_shape": 3},
                 "<-": {"learning_rate": 0.05,
                        "gradient_moment": 0.9}}],
        decision_config={"max_epochs": max_epochs})
    wf._max_fires = 10 ** 6
    wf.initialize(device=XLADevice())
    wf.run()
    return wf


@pytest.fixture(scope="module")
def bundle(tmp_path_factory):
    """One trained forward bundle shared by the whole module (the
    cache key includes the program digest, not the test)."""
    from znicz_tpu.utils.config import reset_root
    reset_root()
    path = str(tmp_path_factory.mktemp("aotb") / "model.npz")
    _train_workflow("aot_bundle").export_forward(path)
    return path


@pytest.fixture(autouse=True)
def _fresh_cache_instances(monkeypatch):
    """Per-test isolation: no inherited store (the suite-level opt-in
    env must not leak in) and no memoized instance across tests."""
    monkeypatch.delenv("ZNICZ_AOT_CACHE", raising=False)
    aot_cache._caches.clear()
    yield
    aot_cache._caches.clear()


def test_disabled_by_default(bundle):
    """No env, config default → no store: warmup traces every
    program and writes nothing anywhere."""
    assert aot_cache.active_cache() is None
    m = ExportedModel.load(bundle, max_batch=4)
    assert m.warmup() == m.compile_count > 0
    assert m.load_count == 0


def test_serving_roundtrip_bitwise(bundle, tmp_path):
    """A second process image (modeled by a fresh model instance over
    the same store) deserializes every bucket program — zero compiles
    — and replies bitwise-equal to the traced arm."""
    root.common.engine.aot_cache = str(tmp_path / "store")
    m1 = ExportedModel.load(bundle, max_batch=8)
    n1 = m1.warmup()
    assert n1 == m1.compile_count > 0 and m1.load_count == 0

    compiles0 = _counter("znicz_xla_compiles_total",
                         site="serving-aot")
    m2 = ExportedModel.load(bundle, max_batch=8)
    n2 = m2.warmup()
    assert n2 == n1
    assert m2.compile_count == 0, "warm store still traced"
    assert m2.load_count == n1
    assert _counter("znicz_xla_compiles_total",
                    site="serving-aot") == compiles0, \
        "a deserialized load was counted as a compile"

    x = np.random.RandomState(3).randn(8, 10).astype(np.float32)
    assert np.array_equal(np.asarray(m1(x)), np.asarray(m2(x)))


def test_warmup_counts_resident_programs(bundle, tmp_path):
    """``warmup()`` reports programs made RESIDENT (compiled OR
    loaded) this call — and 0 when everything is already live."""
    root.common.engine.aot_cache = str(tmp_path / "store")
    m = ExportedModel.load(bundle, max_batch=4)
    first = m.warmup()
    assert first == m.compile_count + m.load_count > 0
    assert m.warmup() == 0


def test_corrupt_entry_quarantined_and_refilled(bundle, tmp_path):
    """On-disk rot: the digest gate quarantines the entry (counted,
    evidence kept), the site falls back to tracing bitwise-equal, and
    the re-trace re-publishes a good entry."""
    store = tmp_path / "store"
    root.common.engine.aot_cache = str(store)
    m1 = ExportedModel.load(bundle, max_batch=2)
    m1.warmup()
    x = np.random.RandomState(5).randn(2, 10).astype(np.float32)
    ref = np.asarray(m1(x))

    victim = sorted(glob.glob(str(store / "*.bin")))[0]
    blob = bytearray(open(victim, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    open(victim, "wb").write(bytes(blob))

    corrupt0 = _counter("znicz_aot_cache_total",
                        site="serving-aot", outcome="corrupt")
    recov0 = _counter("znicz_recoveries_total",
                      kind="aotcache_fallback")
    m2 = ExportedModel.load(bundle, max_batch=2)
    m2.warmup()
    assert m2.compile_count == 1, "corrupt entry did not re-trace"
    assert _counter("znicz_aot_cache_total", site="serving-aot",
                    outcome="corrupt") == corrupt0 + 1
    assert _counter("znicz_recoveries_total",
                    kind="aotcache_fallback") == recov0 + 1
    assert glob.glob(str(store / "*.quarantined")), \
        "quarantine evidence missing"
    assert os.path.exists(victim), "re-trace did not refill the slot"
    assert np.array_equal(ref, np.asarray(m2(x)))


def test_key_mismatch_is_a_miss(tmp_path):
    """The store answers ONLY the exact key — a near-miss (any field
    of the tuple differs) deserializes nothing."""
    import jax
    import jax.numpy as jnp
    root.common.engine.aot_cache = str(tmp_path / "store")
    cache = aot_cache.active_cache()
    x = jnp.zeros((4,), jnp.float32)
    compiled = jax.jit(lambda a: a * 2).lower(x).compile()
    struct = aot_cache.struct_token(
        (jax.ShapeDtypeStruct((4,), jnp.float32),))
    key = aot_cache.entry_key("t", digest="d", geometry=(4,),
                              structs=struct, donate=())
    cache.put(key, compiled, "test", meta={})
    assert cache.get(key, "test") is not None
    near = aot_cache.entry_key("t", digest="d", geometry=(8,),
                               structs=struct, donate=())
    assert near != key
    assert cache.get(near, "test") is None


def test_size_bound_evicts_oldest(tmp_path):
    """``engine.aot_cache_bytes`` bounds the store: oldest entries
    leave first, the newest always survives its own put."""
    import jax
    import jax.numpy as jnp
    root.common.engine.aot_cache = str(tmp_path / "store")
    cache = aot_cache.active_cache()
    x = jnp.zeros((4,), jnp.float32)
    one = jax.jit(lambda a: a + 1).lower(x).compile()
    probe_key = aot_cache.entry_key("probe", digest="d", geometry=(),
                                    structs="s", donate=())
    cache.put(probe_key, one, "test", meta={})
    entry_bytes = cache.total_bytes()
    # the bound is read when the store opens — reopen under it
    root.common.engine.aot_cache_bytes = int(entry_bytes * 2.5)
    aot_cache._caches.clear()
    cache = aot_cache.active_cache()

    keys = [probe_key]
    for i in (2, 3, 4):
        k = aot_cache.entry_key(f"probe{i}", digest="d", geometry=(),
                                structs="s", donate=())
        compiled = jax.jit(lambda a, i=i: a + i).lower(x).compile()
        cache.put(k, compiled, "test", meta={})
        keys.append(k)
    assert cache.total_bytes() <= int(entry_bytes * 2.5)
    assert cache.get(keys[0], "test") is None, "oldest survived"
    assert cache.get(keys[-1], "test") is not None, "newest evicted"


def test_region_roundtrip_identical_weights(tmp_path):
    """Two identical training runs over one store: the second run's
    region programs all deserialize (compile counter flat, hit counter
    moving) and its trained weights are bitwise-identical."""
    root.common.engine.aot_cache = str(tmp_path / "store")
    wf1 = _train_workflow("aot_region", max_epochs=2)
    w1 = [np.asarray(u.weights).copy() for u in wf1.forwards]

    def all_compiles() -> float:
        fam = obs_metrics.REGISTRY.get("znicz_xla_compiles_total")
        return sum(float(c.value) for _, c in fam.items())

    def region_hits() -> float:
        fam = obs_metrics.REGISTRY.get("znicz_aot_cache_total")
        return sum(float(c.value) for key, c in fam.items()
                   if key[0].startswith("region:") and key[1] == "hit")

    compiles0, hits0 = all_compiles(), region_hits()
    wf2 = _train_workflow("aot_region", max_epochs=2)
    assert all_compiles() == compiles0, "second run re-traced a region"
    assert region_hits() > hits0, "region programs never deserialized"
    for a, b in zip(w1, wf2.forwards):
        assert np.array_equal(a, np.asarray(b.weights)), \
            "deserialized training diverged from traced training"


def test_publish_carries_programs(bundle, tmp_path):
    """``publish_bundle`` packs the store's matching-digest entries
    beside the weights; a watcher on a cold host imports them and the
    next serving process warms with zero compiles."""
    from znicz_tpu.resilience.publisher import (PublicationWatcher,
                                                publish_bundle)
    root.common.engine.aot_cache = str(tmp_path / "pub_store")
    wf = _train_workflow("aot_pub")
    pub = str(tmp_path / "handoff")
    publish_bundle(wf, pub, prefix="m")
    # populate the store for THIS architecture, then publish again so
    # the pack carries the programs
    v1 = sorted(glob.glob(os.path.join(pub, "m_v*.npz")))[0]
    m1 = ExportedModel.load(v1, max_batch=4)
    m1.warmup()
    _, v2 = publish_bundle(wf, pub, prefix="m")
    assert os.path.exists(aot_cache._pack_path(v2)), \
        "no programs pack beside the bundle"

    # cold host: fresh store, watcher imports the pack
    root.common.engine.aot_cache = str(tmp_path / "cold_store")
    aot_cache._caches.clear()
    got = PublicationWatcher(pub, prefix="m").poll()
    assert got is not None
    assert aot_cache.active_cache().entries(), "pack not imported"
    m2 = ExportedModel.load(v2, max_batch=4)
    m2.warmup()
    assert m2.compile_count == 0 and m2.load_count > 0
    x = np.random.RandomState(7).randn(4, 10).astype(np.float32)
    assert np.array_equal(np.asarray(m1(x)), np.asarray(m2(x)))


def test_corrupt_pack_rejected_weights_survive(bundle, tmp_path):
    """A rotted programs pack must not poison the store OR block the
    weights: import is refused (counted), the bundle still serves."""
    from znicz_tpu.resilience.publisher import (PublicationWatcher,
                                                publish_bundle)
    root.common.engine.aot_cache = str(tmp_path / "pub_store")
    wf = _train_workflow("aot_pubrot")
    pub = str(tmp_path / "handoff")
    publish_bundle(wf, pub, prefix="m")
    v1 = sorted(glob.glob(os.path.join(pub, "m_v*.npz")))[0]
    ExportedModel.load(v1, max_batch=4).warmup()
    _, v2 = publish_bundle(wf, pub, prefix="m")
    pack = aot_cache._pack_path(v2)
    blob = bytearray(open(pack, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    open(pack, "wb").write(bytes(blob))

    root.common.engine.aot_cache = str(tmp_path / "cold_store")
    aot_cache._caches.clear()
    recov0 = _counter("znicz_recoveries_total",
                      kind="aotcache_fallback")
    got = PublicationWatcher(pub, prefix="m").poll()
    assert got is not None, "corrupt pack blocked the weights"
    assert not aot_cache.active_cache().entries(), \
        "corrupt pack entries reached the store"
    assert _counter("znicz_recoveries_total",
                    kind="aotcache_fallback") > recov0


def test_respecialize_guard_falls_back_on_sharding_change():
    """A persisted ``Compiled`` is pinned to the input shardings it was
    lowered with; on a mesh the compiler assigns shardings to a step's
    outputs, which become the next fire's inputs — the guard must hand
    the variant to a lazy jit (counted as a compile) instead of
    surfacing the dispatch ``ValueError``."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from znicz_tpu.accelerated_units import JitRegion

    devices = jax.devices()
    if len(devices) < 2:
        pytest.skip("needs a multi-device mesh")
    mesh = Mesh(np.array(devices).reshape(len(devices), 1),
                ("data", "model"))

    def fn(x):
        return x * 2.0

    x = np.arange(16, dtype=np.float32)
    prog = jax.jit(fn).lower(x).compile()
    site = "region:respec_guard_test"
    wrapped = JitRegion._respecialize_guard(prog, fn, (), site)
    np.testing.assert_array_equal(np.asarray(wrapped(x)), x * 2)

    before = _counter("znicz_xla_compiles_total", site=site)
    sharded = jax.device_put(
        x, NamedSharding(mesh, PartitionSpec("data")))
    out = wrapped(sharded)  # raises without the guard
    np.testing.assert_array_equal(np.asarray(out), x * 2)
    assert _counter("znicz_xla_compiles_total",
                    site=site) == before + 1
    # and the fallback keeps serving later fires
    np.testing.assert_array_equal(np.asarray(wrapped(sharded)), x * 2)


def test_one_device_program_loads_with_eight_devices_visible(tmp_path):
    """The jax 0.9 regression kept as a test: ``deserialize_and_load``
    left to its default loads a program over EVERY visible device, and
    a one-device program then refuses its one-shard operands
    ("expected 8 shards, got [1]").  The store records the devices a
    program was compiled for and loads it onto those — here the
    fourth of the suite's eight CPU devices."""
    import jax
    import jax.numpy as jnp

    devices = jax.devices()
    assert len(devices) == 8
    x = jax.device_put(jnp.arange(12.0).reshape(3, 4), devices[3])
    compiled = jax.jit(lambda a: a * 2.0 + 1.0).lower(x).compile()
    cache = aot_cache.AotCache(str(tmp_path / "store"))
    assert cache.put("k" * 64, compiled, "test")
    loaded = cache.get("k" * 64, "test")
    assert loaded is not None and cache.corrupt == 0
    out = loaded(x)
    assert out.devices() == {devices[3]}
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x) * 2 + 1)


def test_program_from_jax_compilation_cache_is_not_stored(
        tmp_path, monkeypatch):
    """An executable JAX's own persistent cache handed back loads from
    this store but fails at dispatch when serialized a second time, so
    the store refuses any program whose compile may have been such a
    hit: a hit of JAX's cache between the key's miss and its put."""
    import jax
    import jax.numpy as jnp

    x = jnp.ones((2, 2))
    compiled = jax.jit(lambda a: a + 1.0).lower(x).compile()
    cache = aot_cache.AotCache(str(tmp_path / "store"))
    assert cache.get("a" * 64, "test") is None            # miss
    monkeypatch.setattr(aot_cache, "_jax_cache_hits",
                        aot_cache._jax_cache_hits + 1)    # a JAX hit
    assert not cache.put("a" * 64, compiled, "test")
    assert cache.entries() == []
    # a native compile (no JAX-cache hit since the miss) is stored
    assert cache.get("b" * 64, "test") is None
    assert cache.put("b" * 64, compiled, "test")
    assert [key for key, _meta in cache.entries()] == ["b" * 64]
