"""How the host draws random parameters (``utils.prng``, PR 49).

- a tensor of at most ``CHUNK`` elements is ``normal`` / ``uniform``
  of the generator's own stream, cast: the values every other test of
  this suite was written against;
- a larger tensor is drawn chunk by chunk, each from a stream of its
  own seeded from the parent's, so its values do not depend on the
  number of threads that drew it, and ``get_state`` / ``set_state``
  still carry everything that decides them;
- its law is the one the filling names, its chunks are not copies,
  its dtype and shape are as asked;
- the parent stream advances by exactly the seeds drawn;
- telemetry on or off, the values are the same.
"""

from __future__ import annotations

import math

import numpy as np
import pytest

from znicz_tpu.ops.nn_units import Forward
from znicz_tpu.utils import prng
from znicz_tpu.utils.config import root

C = prng.CHUNK
#: 2.5 chunks as a 3-D slab whose last chunk is cut short
SLAB = (5, 1024, 512)
SMALL_SHAPES = [(), (7,), (3, 5), (4, 3, 2, 2), (C,), (1024, C // 1024)]


def _with_workers(monkeypatch, workers: int) -> None:
    monkeypatch.setattr(prng, "_workers", lambda: workers)


# ----------------------------------------------------------------------
# (a) one chunk: today's draw, value for value
# ----------------------------------------------------------------------
@pytest.mark.parametrize("shape", SMALL_SHAPES, ids=str)
def test_a_tensor_of_one_chunk_is_the_streams_own_normal(shape):
    got = prng.RandomGenerator(11).fill_normal(shape, 0.5, 0.02)
    want = np.random.default_rng(11).normal(0.5, 0.02, size=shape)
    assert got.dtype == np.float32 and got.shape == tuple(shape)
    np.testing.assert_array_equal(got, want.astype(np.float32))


@pytest.mark.parametrize("shape", SMALL_SHAPES, ids=str)
def test_a_tensor_of_one_chunk_is_the_streams_own_uniform(shape):
    got = prng.RandomGenerator(12).fill_uniform(shape, -0.3, 0.1)
    want = np.random.default_rng(12).uniform(-0.3, 0.1, size=shape)
    assert got.dtype == np.float32 and got.shape == tuple(shape)
    np.testing.assert_array_equal(got, want.astype(np.float32))


def test_small_fills_follow_each_other_on_one_stream():
    gen = prng.RandomGenerator(13)
    ref = np.random.default_rng(13)
    for shape in ((4, 4), (C,), (9,)):
        np.testing.assert_array_equal(
            gen.fill_uniform(shape, -1.0, 1.0),
            ref.uniform(-1.0, 1.0, size=shape).astype(np.float32))
        np.testing.assert_array_equal(
            gen.fill_normal(shape, 0.0, 1.0, dtype=np.float64),
            ref.normal(0.0, 1.0, size=shape))


# ----------------------------------------------------------------------
# (b) several chunks: the pool's size decides nothing
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def slabs():
    """The slab of each law as a pool of the process's own size draws
    it, with the generator's state before the draw."""
    out = {}
    for law in ("normal", "uniform"):
        gen = prng.RandomGenerator(21)
        gen.fill_uniform((3,), 0.0, 1.0)        # not a fresh stream
        state = gen.get_state()
        out[law] = (state, _fill(gen, law))
    return out


def _fill(gen, law: str, shape=SLAB, dtype=np.float32):
    if law == "normal":
        return gen.fill_normal(shape, 0.25, 0.02, dtype=dtype)
    return gen.fill_uniform(shape, -0.05, 0.15, dtype=dtype)


@pytest.mark.parametrize("law", ["normal", "uniform"])
@pytest.mark.parametrize("workers", [1, 2, 5])
def test_a_large_tensor_is_the_same_bytes_for_every_pool(
        monkeypatch, slabs, law, workers):
    state, want = slabs[law]
    _with_workers(monkeypatch, workers)
    gen = prng.RandomGenerator(99)
    gen.set_state(state)
    got = _fill(gen, law)
    assert got.tobytes() == want.tobytes()


@pytest.mark.parametrize("law", ["normal", "uniform"])
def test_a_restored_state_draws_the_large_tensor_again(law):
    gen = prng.RandomGenerator(22)
    state = gen.get_state()
    first = _fill(gen, law)
    moved = _fill(gen, law)
    gen.set_state(state)
    again = _fill(gen, law)
    assert again.tobytes() == first.tobytes()
    assert moved.tobytes() != first.tobytes()


def test_a_chunks_failure_is_raised_to_the_caller(monkeypatch):
    def broken(part, seed, law, a, b):
        raise MemoryError("chunk")
    monkeypatch.setattr(prng, "_fill_chunk", broken)
    with pytest.raises(MemoryError, match="chunk"):
        prng.RandomGenerator(23).fill_normal((C + 1,), 0.0, 1.0)


# ----------------------------------------------------------------------
# (c) the law, the shape, the dtype
# ----------------------------------------------------------------------
def _chunks(arr: np.ndarray) -> list:
    flat = arr.reshape(-1)
    return [flat[i:i + C] for i in range(0, flat.size, C)]


@pytest.mark.parametrize("filling", ["gaussian", "he", "xavier"])
def test_a_large_normal_filling_keeps_its_law(filling):
    prng.get().seed(31)
    fan_in = 1024
    arr = Forward.fill_array(None, SLAB, filling, 0.03, fan_in)
    stddev = {"gaussian": 0.03, "he": math.sqrt(2.0 / fan_in),
              "xavier": math.sqrt(1.0 / fan_in)}[filling]
    assert arr.dtype == np.float32 and arr.shape == SLAB
    n = arr.size
    assert n % C and n > 2 * C
    vals = arr.astype(np.float64)
    assert abs(vals.mean()) < 4 * stddev / math.sqrt(n)
    # the sample stddev of a normal scatters by sigma / sqrt(2 n)
    assert abs(vals.std() - stddev) < 4 * stddev / math.sqrt(2 * n)
    # tails are there: no clipping, no [0, 1) left unscaled
    assert vals.max() > 4 * stddev and vals.min() < -4 * stddev
    parts = _chunks(arr)
    assert len(parts) == 3 and parts[-1].size == n - 2 * C
    assert not np.array_equal(parts[0], parts[1])
    assert not np.array_equal(parts[0][:parts[2].size], parts[2])
    for part in parts:
        assert abs(part.astype(np.float64).mean()) \
            < 4 * stddev / math.sqrt(part.size)


def test_a_large_uniform_filling_keeps_its_law():
    prng.get().seed(32)
    arr = Forward.fill_array(None, SLAB, "uniform", 0.05, 1024)
    assert arr.dtype == np.float32 and arr.shape == SLAB
    n, width = arr.size, 0.1
    vals = arr.astype(np.float64)
    # the bounds are the float32s the unit asked with
    assert arr.min() >= np.float32(-0.05) and arr.max() <= np.float32(0.05)
    # the range is used to its ends
    assert vals.min() < -0.05 + 1e-5 and vals.max() > 0.05 - 1e-5
    sigma = width / math.sqrt(12.0)
    assert abs(vals.mean()) < 4 * sigma / math.sqrt(n)
    # var of u^2's estimate: sigma^2 * sqrt(4/5) / sqrt(n)
    assert abs(vals.var() - sigma ** 2) \
        < 4 * sigma ** 2 * math.sqrt(0.8 / n)
    parts = _chunks(arr)
    assert not np.array_equal(parts[0], parts[1])
    assert not np.array_equal(parts[0][:parts[2].size], parts[2])


@pytest.mark.parametrize("law", ["normal", "uniform"])
@pytest.mark.parametrize("dtype", [np.float32, np.float64, np.float16],
                         ids=lambda d: np.dtype(d).name)
def test_a_large_tensor_has_the_dtype_and_shape_asked(law, dtype):
    shape = (2, C // 2 + 3)
    arr = _fill(prng.RandomGenerator(33), law, shape, dtype)
    assert arr.dtype == dtype and arr.shape == shape
    assert arr.flags.c_contiguous and np.isfinite(arr).all()
    lo, hi = (0.25 - 0.2, 0.25 + 0.2) if law == "normal" \
        else (-0.05, 0.15)
    assert lo <= arr.min() and arr.max() <= hi
    want = 0.25 if law == "normal" else 0.05
    assert abs(arr.astype(np.float64).mean() - want) < 1e-3


def test_an_int_for_a_shape_is_a_vector():
    arr = prng.RandomGenerator(34).fill_normal(C + 5, 0.0, 1.0)
    assert arr.shape == (C + 5,)


# ----------------------------------------------------------------------
# (d) the parent stream advances by the seeds drawn, and no more
# ----------------------------------------------------------------------
@pytest.mark.parametrize("workers", [1, 2, 5])
def test_the_next_small_tensor_does_not_depend_on_the_pool(
        monkeypatch, workers):
    _with_workers(monkeypatch, workers)
    gen = prng.RandomGenerator(41)
    large = gen.fill_normal(SLAB, 0.0, 1.0)
    after = gen.fill_uniform((6,), -1.0, 1.0)
    ref = np.random.default_rng(41)
    seeds = ref.integers(0, 1 << 64, size=3, dtype=np.uint64)
    np.testing.assert_array_equal(
        after, ref.uniform(-1.0, 1.0, size=(6,)).astype(np.float32))
    # and a chunk is the standard normal of its own seed's stream
    np.testing.assert_array_equal(
        _chunks(large)[1],
        np.random.Generator(np.random.PCG64(int(seeds[1])))
        .standard_normal(C, dtype=np.float32))


def test_a_tensor_one_element_over_a_chunk_takes_two_seeds():
    gen, ref = prng.RandomGenerator(42), np.random.default_rng(42)
    gen.fill_uniform((C + 1,), 0.0, 1.0)
    ref.integers(0, 1 << 64, size=2, dtype=np.uint64)
    assert gen.numpy.bit_generator.state == ref.bit_generator.state


# ----------------------------------------------------------------------
# (e) telemetry decides no value
# ----------------------------------------------------------------------
@pytest.mark.parametrize("shape", [(3, 5), SLAB], ids=["stream", "chunked"])
def test_telemetry_off_takes_the_same_values(shape):
    on = prng.RandomGenerator(51)
    want = (on.fill_normal(shape, 0.0, 0.1),
            on.fill_uniform(shape, -1.0, 1.0))
    root.common.engine.telemetry = False
    try:
        off = prng.RandomGenerator(51)
        got = (off.fill_normal(shape, 0.0, 0.1),
               off.fill_uniform(shape, -1.0, 1.0))
    finally:
        root.common.engine.telemetry = True
    assert got[0].tobytes() == want[0].tobytes()
    assert got[1].tobytes() == want[1].tobytes()
    assert on.get_state()["numpy_state"] == off.get_state()["numpy_state"]
