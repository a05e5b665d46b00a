"""How the benchmark reaches the system under test: the layer table of
a configuration, options set the way a user sets them, the backend
object, a Vector's value on the host.  Shared by the drivers."""

from __future__ import annotations

import importlib

import numpy as np

from .. import flops


def layer_table(config: dict) -> list:
    """The ``StandardWorkflow`` layer table: written out in the
    configuration's file, or built by the sample the file names."""
    spec = config["workflow"]
    if "layers" in spec:
        return flops.expand(spec["layers"])
    source = spec["layers_from"]
    module = importlib.import_module(source["module"])
    return getattr(module, source["function"])(dict(source["cfg"]))


class engine_options:
    """``root.common.engine`` options for the length of a block, the
    way a user sets them, put back afterwards."""

    def __init__(self, options: dict) -> None:
        self.options = options

    def __enter__(self):
        from znicz_tpu.utils.config import root
        engine = root.common.engine
        self.old = {k: engine.get(k, None) for k in self.options}
        for key, value in self.options.items():
            setattr(engine, key, value)

    def __exit__(self, *exc):
        from znicz_tpu.utils.config import root
        for key, value in self.old.items():
            if value is not None:
                setattr(root.common.engine, key, value)


def make_device(ctx):
    """The backend object: the TPU (``TPUDevice`` raises where there
    is none)."""
    from znicz_tpu.backends import TPUDevice, XLADevice
    if ctx.cell.chips != 1:
        raise ValueError(f"{ctx.cell.name}: the drivers place a cell on "
                         f"one chip; a mesh cell brings its own placement")
    return (XLADevice if ctx.toy else TPUDevice)()


def host(vec) -> np.ndarray:
    """A Vector's device value as float32 on the host."""
    vec.map_read()
    return np.asarray(vec.mem).astype(np.float32)


def head_rows(vec, n: int) -> np.ndarray:
    """The first ``n`` rows of a Vector's device value, float32, cut
    on the device so that only those rows cross to the host."""
    import jax.numpy as jnp
    return np.asarray(vec.devmem[:n].astype(jnp.float32))
