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
"""

from __future__ import annotations

import numpy as np

import jax

from znicz_tpu.observe import metrics as _metrics
from znicz_tpu.observe import tracing as _tracing
from znicz_tpu.utils.config import root


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
        return self._fill(self.numpy.uniform, vmin, vmax, shape, dtype)

    def fill_normal(self, shape, mean: float = 0.0, stddev: float = 1.0,
                    dtype=np.float32) -> np.ndarray:
        return self._fill(self.numpy.normal, mean, stddev, shape, dtype)

    @staticmethod
    def _fill(draw, a: float, b: float, shape, dtype) -> np.ndarray:
        """The draw and its cast: the host's time making random
        parameters is a span (``param_fill``, a child of the unit
        that asked) and a phase of ``znicz_setup_seconds``."""
        if not _metrics.enabled():
            return draw(a, b, size=shape).astype(dtype)
        with _tracing.TRACER.span("param_fill", cat="setup") as span:
            out = draw(a, b, size=shape).astype(dtype)
            span.set(bytes=int(out.nbytes))
        _metrics.setup_seconds("param_fill").inc(span.dur_us / 1e6)
        return out

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
