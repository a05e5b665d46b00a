"""``drivers/train_lm.py`` for a model whose expert layers take their
router's logits from another tensor than the experts read — the
block's input, two sublayers back and un-normed (``ops/moe.py``,
``route_from``; SmallThinker).  That file is loaded by name, as it
loads ``train``, and everything is its own code, run and not copied:
the data, the workflow, the warm-up, the window, the stepping back to
the last step's parameters, ``check`` with its limits and its bf16
controls, the loss, the programs built, the steps skipped.

What differs, and why this is a file of its own: ``train_lm``'s
``check_router`` reckons the reference's logits from ``unit.input``
under the unit's ``pre_norm`` — for such a layer the WRONG tensor, so a
correct program would read ``correct: false`` there.  Here, where a
``moe`` layer states ``route_from``, that function is handed the unit
with the Vector the unit names as its router's input
(``unit.route_input``) in ``input``'s place and the layer's spec
without its ``pre_norm``: the same lines then hold the system's logits
to ``reference.route`` of that tensor as it is, and every chosen
expert to the reference's top k.  A layer without ``route_from`` goes
through untouched.  And ``run`` first looks whether the program knows
every option the table states (:func:`unknown_options`): a program from
before ``route_from`` and ``act`` fails at once, by the option's name.
"""

from __future__ import annotations

import types

from znbench.harness import discovery

train_lm = discovery.load_module("drivers", "train_lm")
_check_router = train_lm.check_router


def check_router(reference, params: dict, layers: list, wf, i: int,
                 n: int) -> dict:
    spec = layers[i]["->"]
    if not spec.get("route_from"):
        return _check_router(reference, params, layers, wf, i, n)
    unit = wf.forwards[i]
    routed = types.SimpleNamespace(
        input=unit.route_input, router_logits=unit.router_logits,
        last_choice=unit.last_choice)
    as_it_is = {key: value for key, value in spec.items()
                if key != "pre_norm"}
    return _check_router(
        reference, params, {i: {"->": as_it_is}},
        types.SimpleNamespace(forwards={i: routed}), i, n)


def unknown_options(layers: list) -> list:
    """``(layer index, type, option)`` for every forward option of the
    table that the program's unit of that type names nowhere among its
    constructors' parameters.  A unit takes an option it does not know
    in silence (``**kwargs`` down to the base), so a program without
    ``route_from`` or ``act`` would train ANOTHER model under this
    configuration's name, for a whole run, before the router's check
    found no ``route_input`` to read."""
    import inspect

    from znicz_tpu.models.standard_workflow import layer_type
    found = []
    for i, layer in enumerate(layers):
        known = set()
        for cls in layer_type(layer["type"]).__mro__:
            init = cls.__dict__.get("__init__")
            if init is not None:
                known |= set(inspect.signature(init).parameters)
        found += [(i, layer["type"], option)
                  for option in layer.get("->", {}) if option not in known]
    return found


def run(ctx):
    """``train_lm.run``, once the program is seen to know every option
    the configuration's table states: else no result line, at once."""
    unknown = unknown_options(train_lm.layer_table(ctx.cell.config))
    if unknown:
        raise discovery.BenchmarkError(
            f"{ctx.cell.name}: the program knows no option "
            + ", ".join(f"{option!r} of layer {i} ({kind})"
                        for i, kind, option in unknown)
            + f" — it cannot run configuration {ctx.cell.config_name}")
    return train_lm.run(ctx)


# this module's own copy of ``train_lm`` (``load_module`` makes one a
# call): its ``check`` finds the function above under the old name
train_lm.check_router = check_router
# … and what the cells' controls (``benchmarks/controls.py``) reach a
# driver for
build, check, train = train_lm.build, train_lm.check, train_lm.train
