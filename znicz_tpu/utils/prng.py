"""Seeded deterministic random generators (reference: ``veles/prng/``).

The reference shipped seed files and generated random streams on-device
with custom kernels; bit-exact parity with those streams is impossible
(documented in SURVEY.md §2.3) — the parity target is statistical.

Design: one named registry of :class:`RandomGenerator` objects
(``prng.get()`` returns the default, like the reference's ``rnd``).
Each generator owns

- a host ``numpy.random.Generator`` for control-plane randomness
  (dataset shuffles, weight init done host-side), and
- a jax PRNG key chain for device randomness; ``key()`` splits off a
  fresh subkey statefully for eager use, while jit regions carry key
  state as an explicit leaf (see ``accelerated_units``).

Weight fills (``fill_normal`` / ``fill_uniform``) draw a tensor as
``ceil(n / CHUNK)`` chunks of :data:`CHUNK` elements of its flattened
(C-order) form.  A tensor of ONE chunk — every bias, norm and router,
every tensor of a toy model — is ``normal(...)`` / ``uniform(...)``
of the generator's own stream, cast: the draw this module has always
made, value for value.  A larger tensor takes one 64-bit seed per
chunk from that stream, and chunk ``i`` is drawn by a
``Generator(PCG64(seed_i))`` of its own at the target dtype straight
into its slice of the one output, by a pool of host threads (numpy's
draws release the GIL): no float64 copy of the tensor, no cast pass.
What decides the values is the generator's state, the order and
shapes of the fills and :data:`CHUNK` — never the pool's size, which
is taken from the cores the process may use, nor which thread drew
which chunk: the chunk → seed map is fixed before a thread runs, and
the parent stream advances by exactly the seeds drawn, so
``get_state()`` / ``set_state()`` still carry everything.  The law is
the one the filling names; above a chunk the stream is another one of
that law (the statistical parity of SURVEY.md §2.3).
"""

from __future__ import annotations

import functools
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import jax

from znicz_tpu.observe import metrics as _metrics
from znicz_tpu.observe import tracing as _tracing
from znicz_tpu.utils.config import root

#: elements of one chunk of a weight fill.  Part of what decides a
#: large tensor's values (like the seed), so a constant of the format
#: and not a setting
CHUNK = 1 << 20


def _workers() -> int:
    """Threads a large fill is drawn by: the cores this process may
    use, capped where more of them stop making the draw faster."""
    return min(8, len(os.sched_getaffinity(0)))


@functools.cache
def _pool(workers: int) -> ThreadPoolExecutor:
    """The process's fill threads, started by its first large fill."""
    return ThreadPoolExecutor(workers, thread_name_prefix="znicz-fill")


def _fill_chunk(part: np.ndarray, seed: int, law: str, a: float,
                b: float) -> None:
    """Draw one chunk in place from a stream of its own: ``normal``
    with mean ``a`` and stddev ``b``, or ``uniform`` on [``a``, ``b``)."""
    rng = np.random.Generator(np.random.PCG64(seed))
    if law == "normal":
        rng.standard_normal(dtype=part.dtype, out=part)
        scale = b
    else:
        rng.random(dtype=part.dtype, out=part)
        scale = b - a
    part *= part.dtype.type(scale)
    part += part.dtype.type(a)


class RandomGenerator:
    def __init__(self, seed: int | None = None, name: str = "default") -> None:
        self.name = name
        self.seed(seed if seed is not None else int(root.common.seed))

    def seed(self, seed: int) -> None:
        self._seed = int(seed)
        self.numpy = np.random.default_rng(self._seed)
        self._key = jax.random.key(self._seed)

    @property
    def initial_seed(self) -> int:
        return self._seed

    def key(self) -> jax.Array:
        """Split off a fresh jax PRNG subkey (stateful, host-side)."""
        self._key, sub = jax.random.split(self._key)
        return sub

    # --- host-side convenience used for weight fills -------------------
    def fill_uniform(self, shape, vmin: float, vmax: float,
                     dtype=np.float32) -> np.ndarray:
        return self._fill("uniform", vmin, vmax, shape, dtype)

    def fill_normal(self, shape, mean: float = 0.0, stddev: float = 1.0,
                    dtype=np.float32) -> np.ndarray:
        return self._fill("normal", mean, stddev, shape, dtype)

    def _fill(self, law: str, a: float, b: float, shape,
              dtype) -> np.ndarray:
        """One tensor of ``law`` (see the module's docstring for what
        a chunk is).  The host's time making random parameters is ONE
        span per tensor (``param_fill``, a child of the unit that
        asked: wall time of the calling thread, the pool's threads
        open none) and a phase of ``znicz_setup_seconds``."""
        if not _metrics.enabled():
            return self._draw(law, a, b, shape, dtype)[0]
        with _tracing.TRACER.span("param_fill", cat="setup") as span:
            out, chunks, workers = self._draw(law, a, b, shape, dtype)
            span.set(bytes=int(out.nbytes), chunks=chunks, workers=workers)
        _metrics.setup_seconds("param_fill").inc(span.dur_us / 1e6)
        _metrics.param_fill_bytes(
            "chunked" if chunks > 1 else "stream").inc(out.nbytes)
        return out

    def _draw(self, law: str, a: float, b: float, shape,
              dtype) -> tuple[np.ndarray, int, int]:
        """``(tensor, chunks, threads that drew it)``."""
        n = int(np.prod(shape))
        if n <= CHUNK:
            draw = getattr(self.numpy, law)
            return draw(a, b, size=shape).astype(dtype), 1, 1
        # numpy draws at these two widths; any other is cast from f32
        drawn = np.dtype(dtype)
        if drawn not in (np.float32, np.float64):
            drawn = np.dtype(np.float32)
        out = np.empty(shape, drawn)
        flat = out.reshape(-1)
        parts = [flat[i:i + CHUNK] for i in range(0, n, CHUNK)]
        seeds = self.numpy.integers(0, 1 << 64, size=len(parts),
                                    dtype=np.uint64)
        workers = _workers()
        # list(): a chunk's exception is raised here, not dropped
        list(_pool(workers).map(
            functools.partial(_fill_chunk, law=law, a=a, b=b),
            parts, seeds.tolist()))
        return out.astype(dtype, copy=False), len(parts), workers

    def shuffle(self, arr: np.ndarray) -> None:
        self.numpy.shuffle(arr)

    def permutation(self, n: int) -> np.ndarray:
        return self.numpy.permutation(n)

    def randint(self, low: int, high: int, size=None):
        return self.numpy.integers(low, high, size=size)

    def get_state(self) -> dict:
        """Serializable state for snapshot/resume trajectory fidelity."""
        return {
            "seed": self._seed,
            "numpy_state": self.numpy.bit_generator.state,
            "jax_key": np.asarray(jax.random.key_data(self._key)),
        }

    def set_state(self, state: dict) -> None:
        self._seed = int(state["seed"])
        self.numpy = np.random.default_rng(self._seed)
        self.numpy.bit_generator.state = state["numpy_state"]
        self._key = jax.random.wrap_key_data(
            np.asarray(state["jax_key"], dtype=np.uint32))


_generators: dict[str, RandomGenerator] = {}


def get(name: str = "default") -> RandomGenerator:
    gen = _generators.get(name)
    if gen is None:
        gen = _generators[name] = RandomGenerator(name=name)
    return gen


def seed_all(seed: int) -> None:
    """Reseed every registered generator (tests / run reproducibility)."""
    root.common.seed = int(seed)
    for gen in _generators.values():
        gen.seed(seed)
    if "default" not in _generators:
        get("default")
