"""``observe.op_scopes()``: which unit, and which phase of it, every
HLO instruction of the compiled region programs belongs to — the
program's own key to a profile's ``fusion.362``."""

import gc
import json
import weakref

import jax
import numpy as np
import pytest

from znicz_tpu import observe
from znicz_tpu.backends import XLADevice
from znicz_tpu.loader.fullbatch import ArrayLoader
from znicz_tpu.models.standard_workflow import StandardWorkflow
from znicz_tpu.observe import metrics as obs_metrics
from znicz_tpu.observe import scopes
from znicz_tpu.utils import prng
from znicz_tpu.utils.config import root

GD = {"learning_rate": 0.05, "gradient_moment": 0.9}


@pytest.fixture(autouse=True)
def _own_records():
    """Every test reads the programs IT dispatched; compile counters
    are deltas, so the opt-in suite store stays out."""
    root.common.engine.aot_cache = False
    scopes.forget()
    yield
    scopes.forget()


def conv_dense(name: str, epochs: int = 1, **loader) -> StandardWorkflow:
    rng = np.random.default_rng(0)
    data = rng.normal(size=(64, 8, 8, 3)).astype(np.float32)
    labels = rng.integers(0, 4, 64).astype(np.int32)
    prng.seed_all(3)
    wf = StandardWorkflow(
        name=name,
        loader_factory=lambda w: ArrayLoader(
            w, train_data=data, train_labels=labels, minibatch_size=4,
            **loader),
        layers=[{"type": "conv_relu",
                 "->": {"n_kernels": 4, "kx": 3, "ky": 3}, "<-": GD},
                {"type": "max_pooling", "->": {"kx": 2, "ky": 2}},
                {"type": "all2all_tanh",
                 "->": {"output_sample_shape": 8}, "<-": GD},
                {"type": "softmax", "->": {"output_sample_shape": 4},
                 "<-": GD}],
        decision_config={"max_epochs": epochs})
    wf._max_fires = 100_000
    wf.initialize(device=XLADevice())
    return wf


def attention_moe(name: str, **expert) -> StandardWorkflow:
    """An embedding, an attention sublayer, an expert layer (with the
    further options ``expert``) and a head."""
    vocab, seq, dim = 29, 8, 16
    ids = np.random.default_rng(6).integers(0, vocab, (8, seq + 1))
    prng.seed_all(21)
    wf = StandardWorkflow(
        name=name,
        loader_factory=lambda w: ArrayLoader(
            w, train_data=ids[:, :-1].astype(np.float32),
            train_labels=ids[:, 1:].astype(np.int32),
            minibatch_size=4, shuffle_limit=0),
        layers=[
            {"type": "embedding",
             "->": {"vocab_size": vocab, "dim": dim}, "<-": GD},
            {"type": "attention",
             "->": {"n_heads": 2, "causal": True, "include_bias": False,
                    "pre_norm": "rms", "residual": True}, "<-": GD},
            {"type": "moe",
             "->": {"n_experts": 4, "top_k": 2, "width": 16,
                    "pre_norm": "rms", "residual": True, **expert},
             "<-": GD},
            {"type": "softmax",
             "->": {"output_sample_shape": vocab, "per_position": True,
                    "include_bias": False}, "<-": GD}],
        decision_config={"max_epochs": 1})
    wf.initialize(device=XLADevice())
    return wf


def seen_units(ops: dict) -> set:
    return {unit for entry in ops.values()
            for unit in ([entry["unit"]] if entry["unit"]
                         else entry["units"])}


def phases_of(ops: dict, unit: str) -> set:
    return {e["phase"] for e in ops.values() if e["unit"] == unit}


def only_program(prefix: str) -> dict:
    found = {name: ops for name, ops in observe.op_scopes().items()
             if name.startswith(prefix)}
    assert len(found) == 1, sorted(observe.op_scopes())
    return next(iter(found.values()))


# ----------------------------------------------------------------------
# the map of a step program
# ----------------------------------------------------------------------
@pytest.mark.parametrize("build", [conv_dense, attention_moe])
def test_every_member_unit_is_in_the_map(build):
    wf = build(f"scopes_{build.__name__}")
    wf.run()
    region = wf._region_unit.region
    ops = only_program(f"znicz_step__{region.name}")
    members = {u.name for u in region.units}
    assert seen_units(ops) == members
    # (at this size XLA may fuse ALL of a small unit into a
    # neighbour's operations: it is then in mixed entries only)
    by_unit = {e["unit"]: e for e in ops.values() if e["unit"]}
    kinds = {u.name: type(u).__name__ for u in region.units}
    assert len(by_unit) >= len(members) - 2
    assert all(e["kind"] == kinds[name] for name, e in by_unit.items())
    for fwd, gd in zip(wf.forwards, wf.gds):
        # a layer's two units are one family: the forward's class
        assert {by_unit[u.name]["family"] for u in (fwd, gd)
                if u.name in by_unit} == {type(fwd).__name__}
        # (an expert layer's router, top k and dispatch plan run under
        # the scope ``route``, forward and pullback: PR 50)
        # … and its experts' rows gathered and put back under the
        # scope ``combine`` (PR 51)
        route = {"route", "combine"} if type(fwd).__name__ == "MoE" \
            else set()
        assert phases_of(ops, fwd.name) <= {"forward"} | route
        assert phases_of(ops, gd.name) \
            <= {"backward", "update", "fingerprint"} | route


@pytest.mark.parametrize("early", [False, True],
                         ids=["router_on_its_own_input",
                              "router_on_the_block_input"])
def test_route_is_a_phase_of_the_expert_layer_forward_and_pullback(early):
    """The router's logits, top k and the dispatch's sort lie under the
    scope ``route`` (``ops/moe.py``): whatever XLA kept of them apart
    from their neighbours reads phase ``route``, in the forward unit
    and — ``transpose(jvp(route))`` — in the backward unit; with
    ``route_from`` the logits come from the block's input and the map
    is the same."""
    from znicz_tpu.observe import scopes
    assert scopes.pattern("route").search("jit(step)/MoE_2/jvp(route)/dot_general")
    assert scopes.pattern("route").search("a/GDMoE_2/transpose(jvp(route))/mul")
    assert not scopes.pattern("route").search("a/MoE_2/jvp()/router_bias/add")
    wf = attention_moe(f"scopes_route_{early}", **(
        {"route_from": "block_input", "act": "relu"} if early else {}))
    wf.run()
    ops = only_program(f"znicz_step__{wf._region_unit.region.name}")
    expert = next(u for u in wf.forwards if type(u).__name__ == "MoE")
    gd = wf.gds[wf.forwards.index(expert)]
    assert "route" in phases_of(ops, expert.name)
    assert phases_of(ops, gd.name) & {"route", "backward"}
    for unit in wf.forwards + wf.gds:
        if unit not in (expert, gd):
            assert "route" not in phases_of(ops, unit.name)


@pytest.mark.parametrize("share", ["dropless", "held_by_gathers",
                                   "held_by_a_scatter_add"])
def test_combine_is_a_phase_of_the_expert_layer_forward_and_pullback(
        share, monkeypatch):
    """The rows of a layer's experts gathered from their tokens and put
    back, weighted and summed, lie under the scope ``combine``
    (``ops/moe.py``) — a dropless layer's, a held share's by gathers and
    by a scatter-add: what XLA kept of them apart from their neighbours
    reads phase ``combine``, in the forward unit (traced under
    ``jax.vjp``: ``jvp(combine)``) and in the backward unit
    (``transpose(jvp(combine))``, the pullbacks of the layer's own
    primitives too), and in no other unit."""
    from znicz_tpu.observe import scopes
    from znicz_tpu.ops import moe
    assert scopes.pattern("combine").search("jit(step)/MoE_2/jvp(combine)/gather")
    assert scopes.pattern("combine").search(
        "a/GDMoE_2/jit(_fit_or_capacity_bwd)/transpose(jvp(combine))/mul")
    assert not scopes.pattern("combine").search("a/MoE_2/jvp(route)/combined/add")
    if share == "held_by_a_scatter_add":
        monkeypatch.setattr(moe, "HELD_GATHER", 0)
    wf = attention_moe(f"scopes_combine_{share}", **(
        {} if share == "dropless" else {"held": (0, 2)}))
    wf.run()
    ops = only_program(f"znicz_step__{wf._region_unit.region.name}")
    expert = next(u for u in wf.forwards if type(u).__name__ == "MoE")
    gd = wf.gds[wf.forwards.index(expert)]
    assert obs_metrics.moe_combine(expert.name, "gather").value \
        == (share != "held_by_a_scatter_add")
    assert "combine" in phases_of(ops, expert.name)
    # (at this size XLA may fuse the pullback's gathers into their
    # neighbours: the scope is then in the text it compiled from)
    assert phases_of(ops, gd.name) & {"combine", "backward"}
    for unit in wf.forwards + wf.gds:
        if unit not in (expert, gd):
            assert "combine" not in phases_of(ops, unit.name)

    def both(*args):
        (y, aux), pullback, _ = jax.vjp(expert.xla_forward, *args,
                                        has_aux=True)
        return pullback((y, aux))

    text = jax.jit(both).lower(*expert.forward_args()).as_text(
        debug_info=True)
    for scope in ("/jvp(combine)", "/transpose(jvp(combine))"):
        assert scope in text, scope


def latent_stack(name: str, **latent) -> StandardWorkflow:
    """An embedding, a latent-K/V attention sublayer (with the further
    options ``latent``), a dense gated MLP and a head."""
    vocab, seq, dim = 29, 32, 64
    ids = np.random.default_rng(7).integers(0, vocab, (8, seq + 1))
    prng.seed_all(22)
    wf = StandardWorkflow(
        name=name,
        loader_factory=lambda w: ArrayLoader(
            w, train_data=ids[:, :-1].astype(np.float32),
            train_labels=ids[:, 1:].astype(np.int32),
            minibatch_size=4, shuffle_limit=0),
        layers=[
            {"type": "embedding",
             "->": {"vocab_size": vocab, "dim": dim}, "<-": GD},
            {"type": "latent_attention",
             "->": {"n_heads": 2, "causal": True, "include_bias": False,
                    "pre_norm": "rms", "residual": True, "kv_latent": 32,
                    "qk_nope": 128, "qk_rope": 64, "v_head_dim": 128,
                    "rope": {"theta": 1e6}, "norm_eps": 1e-6, **latent},
             "<-": GD},
            {"type": "gated_mlp",
             "->": {"width": 96, "pre_norm": "rms", "residual": True},
             "<-": GD},
            {"type": "softmax",
             "->": {"output_sample_shape": vocab, "per_position": True,
                    "include_bias": False}, "<-": GD}],
        decision_config={"max_epochs": 1})
    wf.initialize(device=XLADevice())
    return wf


@pytest.mark.parametrize("latent", [{}, {"q_latent": 48},
                                    {"head_gate": True}],
                         ids=["fused_queries", "query_latent",
                              "head_gate"])
def test_project_and_rotate_norm_are_phases_of_a_latent_layer(latent):
    """A latent-K/V layer's matmuls outside its kernels lie under the
    scope ``project``, the element-wise passes around them under
    ``rotate_norm`` (``ops/attention.py`` ``_latent_forward``, PR 52):
    what XLA kept of each apart from the other reads that phase — in
    the forward unit (``jvp(project)``) and, ``transpose(jvp(…))``, in
    the backward unit — and no other unit has either; ``UNIT_PHASES``
    is where a reader asks whether the program knows a phase: every
    phase a unit class of this process declares."""
    from znicz_tpu.ops import attention, moe
    assert tuple(moe.MoE.PHASES) == ("router_bias", "route", "combine")
    assert attention.MultiHeadAttention.PHASES == {
        "project": scopes.PRODUCTS, "rotate_norm": scopes.ALL}
    assert {*moe.MoE.PHASES, "project", "rotate_norm"} \
        <= set(scopes.UNIT_PHASES)
    assert scopes.pattern("project").search(
        "jit(step)/MultiHeadAttention_2/jvp(project)/dot_general")
    assert scopes.pattern("rotate_norm").search(
        "a/GDMultiHeadAttention_2/transpose(jvp(rotate_norm))/mul")
    assert not scopes.pattern("project").search("a/MoE_2/jvp()/_project_out/add")
    assert not scopes.pattern("rotate_norm").search("a/b/jvp(rotate)/norm/mul")
    wf = latent_stack(f"scopes_latent_{len(latent)}_"
                      f"{next(iter(latent), 'plain')}", **latent)
    wf.run()
    ops = only_program(f"znicz_step__{wf._region_unit.region.name}")
    mixer = wf.forwards[1]
    assert mixer.kv_latent == 32
    gd = wf.gds[1]
    forward, backward = phases_of(ops, mixer.name), phases_of(ops, gd.name)
    assert {"project", "rotate_norm"} <= forward | backward
    assert "project" in forward and "project" in backward
    assert forward <= {"forward", "project", "rotate_norm"}
    assert backward <= {"backward", "update", "fingerprint", "project",
                        "rotate_norm"}
    for unit in wf.forwards + wf.gds:
        if unit not in (mixer, gd):
            assert not phases_of(ops, unit.name) \
                & {"project", "rotate_norm"}

    def both(*args):
        y, pullback = jax.vjp(mixer.xla_forward, *args)
        return pullback(y)

    text = jax.jit(both).lower(*mixer.forward_args()).as_text(
        debug_info=True)
    for scope in ("/jvp(project)", "/transpose(jvp(project))",
                  "/jvp(rotate_norm)", "/transpose(jvp(rotate_norm))"):
        assert scope in text, scope


def test_update_and_fingerprint_are_phases_of_the_backward_unit():
    wf = conv_dense("scopes_phases")
    wf.run()
    ops = only_program("znicz_step__")
    for gd in wf.gds:
        if gd.weights is None or not gd.weights:
            assert phases_of(ops, gd.name) <= {"backward"}, gd.name
            continue
        # momentum, decay, the guard's select under ``update``; the
        # SDC fold inside it under ``fingerprint``; the gradient's
        # own matmuls stay ``backward``
        assert {"update", "fingerprint"} <= phases_of(ops, gd.name), \
            (gd.name, phases_of(ops, gd.name))
    assert {e["phase"] for e in ops.values() if e["unit"]} \
        <= {"forward", "backward", "update", "fingerprint"}


def test_a_fusion_of_two_units_reads_mixed():
    wf = conv_dense("scopes_mixed")
    wf.run()
    ops = only_program("znicz_step__")
    mixed = [e for e in ops.values() if e["unit"] is None]
    members = {u.name for u in wf._region_unit.region.units}
    assert mixed, "XLA fused nothing across units?"
    by_name = {u.name: u for u in wf._region_unit.region.units}
    for entry in mixed:
        assert set(entry) == {"unit", "units", "kinds", "families",
                              "phases"}
        assert len(entry["units"]) > 1 and set(entry["units"]) <= members
        assert entry["kinds"] == [type(by_name[name]).__name__
                                  for name in entry["units"]]
        assert len(entry["families"]) == len(entry["phases"]) \
            == len(entry["units"])


SYNTHETIC = """HloModule jit_znicz_step__r, is_scheduled=true, entry_computation_layout={(f32[8]{0:T(256)})->f32[8]{0:T(256)}}

%region_0.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%a, %b), metadata={op_name="reduce_sum"}
}

%fused_computation (p: f32[8]) -> f32[8] {
  %p = f32[8]{0:T(256)} parameter(0)
  %mul.1 = f32[8]{0:T(256)} multiply(%p, %p), metadata={op_name="jit(znicz_step__r)/GDFc/update/mul"}
  ROOT %sub.1 = f32[8]{0:T(256)} subtract(%mul.1, %p), metadata={op_name="jit(znicz_step__r)/GDFc/update/fingerprint/sub"}
}

%fused_computation.1 (p: f32[8]) -> f32[] {
  %p.1 = f32[8]{0:T(256)} parameter(0)
  %neg.1 = f32[8]{0:T(256)} negate(%p.1), metadata={op_name="jit(znicz_step__r)/GDFc/transpose(jvp(Fc/dot))/neg"}
  %c.1 = f32[] constant(0)
  ROOT %reduce.1 = f32[] reduce(%neg.1, %c.1), dimensions={0}, to_apply=%region_0.1, metadata={op_name="jit(znicz_step__r)/Fc/reduce_sum"}
}

%body (t: (s32[], f32[8])) -> (s32[], f32[8]) {
  %t = (s32[], f32[8]{0:T(256)}) parameter(0)
  %g.1 = f32[8]{0:T(256)} get-tuple-element(%t), index=1
  %fusion.7 = f32[8]{0:T(256)} fusion(%g.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(znicz_step__r)/while/body/GDFc/update/mul"}
  %g.0 = s32[] get-tuple-element(%t), index=0
  ROOT %tuple.1 = (s32[], f32[8]{0:T(256)}) tuple(%g.0, %fusion.7)
}

%cond (t.1: (s32[], f32[8])) -> pred[] {
  %t.1 = (s32[], f32[8]{0:T(256)}) parameter(0)
  ROOT %lt.1 = pred[] constant(true)
}

ENTRY %main.3 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0:T(256)} parameter(0)
  %zero = s32[] constant(0)
  %tuple.0 = (s32[], f32[8]{0:T(256)}) tuple(%zero, %x)
  %while.1 = (s32[], /*index=1*/f32[8]{0:T(8,128)(2,1)}) while(%tuple.0), condition=%cond, body=%body, metadata={op_name="jit(znicz_step__r)/while"}
  %fusion.8 = f32[] fusion(%x), kind=kInput, calls=%fused_computation.1, metadata={op_name="jit(znicz_step__r)/Fc/reduce_sum"}
  %copy.1 = f32[8]{0:T(256)} copy(%x)
  ROOT %custom-call.1 = f32[8]{0:T(256)} custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="jit(znicz_step__r)/Fc/znicz_flash_fwd"}
}
"""


def test_the_text_is_attributed_by_all_the_fused_instructions():
    units = (("Fc", "All2All", "All2All", False),
             ("GDFc", "GradientDescent", "All2All", True))
    ops = scopes.attribute(SYNTHETIC, units)
    # a fusion wholly inside ``update`` with a fold in it reads
    # ``update``; found through the while body (a TPU's tuple type with
    # tiled layouts does not hide the opcode)
    assert ops["fusion.7"] == {"unit": "GDFc", "kind": "GradientDescent",
                               "family": "All2All", "phase": "update"}
    # its root alone says Fc; the body holds a backward instruction
    assert ops["fusion.8"] == {
        "unit": None, "units": ["Fc", "GDFc"],
        "kinds": ["All2All", "GradientDescent"],
        "families": ["All2All", "All2All"],
        "phases": ["forward", "backward"]}
    assert ops["custom-call.1"]["unit"] == "Fc" \
        and ops["custom-call.1"]["phase"] == "forward"
    # no scope, no entry: the scan's while, the compiler's copy, and
    # nothing from inside a fused computation
    assert set(ops) == {"fusion.7", "fusion.8", "custom-call.1"}


PRODUCTS = """HloModule jit_znicz_step__r, is_scheduled=true

%fused_computation (p: f32[8,8], w: bf16[8,8]) -> f32[8,8] {
  %p = f32[8,8]{1,0} parameter(0)
  %w = bf16[8,8]{1,0} parameter(1)
  %zero.1 = f32[] constant(0), metadata={op_name="jit(znicz_step__r)/MoE/jvp(jit(_fit))"}
  %div.1 = f32[8,8]{1,0} multiply(%p, %p), metadata={op_name="jit(znicz_step__r)/Mixer/jvp(rotate_norm)/mul"}
  %cast.1 = bf16[8,8]{1,0} convert(%div.1), metadata={op_name="jit(znicz_step__r)/GDMixer/transpose(jvp(project))/convert_element_type"}
  ROOT %conv.1 = f32[8,8]{1,0} convolution(%cast.1, %w), dim_labels=bf_io->bf, metadata={op_name="jit(znicz_step__r)/GDMixer/transpose(jvp(project))/dot_general"}
}

%fused_computation.1 (p: f32[8,8], w: bf16[8,8]) -> f32[8,8] {
  %p.1 = f32[8,8]{1,0} parameter(0)
  %w.1 = bf16[8,8]{1,0} parameter(1)
  %cast.2 = bf16[8,8]{1,0} convert(%p.1), metadata={op_name="jit(znicz_step__r)/Mixer/jvp(project)/convert_element_type"}
  %conv.2 = f32[8,8]{1,0} convolution(%cast.2, %w.1), dim_labels=bf_io->bf, metadata={op_name="jit(znicz_step__r)/Mixer/jvp(project)/dot_general"}
  %cast.3 = bf16[8,8]{1,0} convert(%conv.2), metadata={op_name="jit(znicz_step__r)/Mixer/jvp()/convert_element_type"}
  ROOT %conv.3 = f32[8,8]{1,0} convolution(%cast.3, %w.1), dim_labels=bf_io->bf, metadata={op_name="jit(znicz_step__r)/Mixer/jvp()/dot_general"}
}

%fused_computation.2 (p: f32[8,8]) -> f32[8,8] {
  %p.2 = f32[8,8]{1,0} parameter(0)
  %mul.2 = f32[8,8]{1,0} multiply(%p.2, %p.2), metadata={op_name="jit(znicz_step__r)/Mixer/jvp(rotate_norm)/mul"}
  ROOT %cast.4 = bf16[8,8]{1,0} convert(%mul.2), metadata={op_name="jit(znicz_step__r)/Mixer/jvp(project)/convert_element_type"}
}

ENTRY %main.3 (x: f32[8,8], w: bf16[8,8]) -> f32[8,8] {
  %x = f32[8,8]{1,0} parameter(0)
  %w.2 = bf16[8,8]{1,0} parameter(1)
  %fusion.1 = f32[8,8]{1,0} fusion(%x, %w.2), kind=kOutput, calls=%fused_computation, metadata={op_name="jit(znicz_step__r)/GDMixer/transpose(jvp(project))/dot_general"}
  %fusion.2 = f32[8,8]{1,0} fusion(%x, %w.2), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(znicz_step__r)/Mixer/jvp()/dot_general"}
  ROOT %fusion.3 = f32[8,8]{1,0} fusion(%x), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(znicz_step__r)/Mixer/jvp(project)/convert_element_type"}
}
"""


def test_a_fusion_around_the_products_of_project_reads_project():
    """``project`` names products (PR 52): a fusion whose every matmul
    lies in it is ``project`` of the matmuls' unit, whatever the
    compiler fused around them — the norm's last multiply of the
    re-made forward, a constant another unit's trace left; one matmul
    outside the scope, or none at all, and the fusion is read by all
    its instructions as ever."""
    phases = (("project", scopes.PRODUCTS), ("rotate_norm", scopes.ALL))
    units = (("Mixer", "MultiHeadAttention", "MultiHeadAttention", False,
              phases),
             ("GDMixer", "GDMultiHeadAttention", "MultiHeadAttention",
              True, phases),
             ("MoE", "MoE", "MoE", False))
    ops = scopes.attribute(PRODUCTS, units)
    assert ops["fusion.1"] == {
        "unit": "GDMixer", "kind": "GDMultiHeadAttention",
        "family": "MultiHeadAttention", "phase": "project"}
    assert ops["fusion.2"]["unit"] == "Mixer" \
        and ops["fusion.2"]["phase"] == "forward"
    assert ops["fusion.3"]["unit"] == "Mixer" \
        and ops["fusion.3"]["phase"] == "forward"


def test_a_unit_declares_its_own_phases():
    """The seam: a layer type lands as a unit — its class declares the
    scopes it opens (``PHASES``), the region hands the declaration in
    with its members, and ``observe/scopes.py`` reads them without
    knowing the unit: one read by all an operation's instructions, one
    by its products, in the forward unit and (the declaration holds
    for the layer's backward unit) wherever its GD carries them."""
    import jax.numpy as jnp
    from znicz_tpu.models.standard_workflow import register_layer_type
    from znicz_tpu.ops import all2all

    class ToyScoped(all2all.All2AllTanh):
        PHASES = {"toy_matmul": scopes.PRODUCTS, "toy_squash": scopes.ALL}

        def xla_run(self) -> None:
            x = self.input.devmem
            with jax.named_scope("toy_matmul"):
                y = self.mxu_dot(jnp, x.reshape(len(x), -1),
                                 self.weights.devmem)
            with jax.named_scope("toy_squash"):
                self.output.devmem = self.activation.fwd(
                    jnp, y + self.bias.devmem)

    assert {"toy_matmul", "toy_squash"} <= set(scopes.UNIT_PHASES)
    with pytest.raises(ValueError, match="PHASES"):
        type("Wrong", (all2all.All2All,), {"PHASES": {"update": "all"}})
    register_layer_type("toy_scoped", ToyScoped)
    rng = np.random.default_rng(0)
    prng.seed_all(3)
    wf = StandardWorkflow(
        name="scopes_toy",
        loader_factory=lambda w: ArrayLoader(
            w, train_data=rng.normal(size=(16, 12)).astype(np.float32),
            train_labels=rng.integers(0, 4, 16).astype(np.int32),
            minibatch_size=4),
        layers=[{"type": "toy_scoped",
                 "->": {"output_sample_shape": 8}, "<-": GD},
                {"type": "softmax", "->": {"output_sample_shape": 4},
                 "<-": GD}],
        decision_config={"max_epochs": 1})
    wf.initialize(device=XLADevice())
    wf.run()
    ops = only_program(f"znicz_step__{wf._region_unit.region.name}")
    toy, gd = wf.forwards[0], wf.gds[0]
    assert isinstance(toy, ToyScoped)
    assert {"toy_matmul", "toy_squash"} <= phases_of(ops, toy.name)
    # the classic GD writes its backward by hand: it opens neither
    assert phases_of(ops, gd.name) <= {"backward", "update",
                                       "fingerprint"}
    for unit in wf.forwards[1:] + wf.gds[1:]:
        assert not phases_of(ops, unit.name) & set(ToyScoped.PHASES)
    # read by its products: the text of the latent layer's test under
    # the toy's scope, and its backward unit handed the declaration
    declared = tuple(ToyScoped.PHASES.items())
    units = (("Mixer", "ToyScoped", "All2AllTanh", False, declared),
             ("GDMixer", "GDTanh", "All2AllTanh", True, declared),
             ("MoE", "MoE", "MoE", False))
    ops = scopes.attribute(
        PRODUCTS.replace("project", "toy_matmul"), units)
    assert ops["fusion.1"]["phase"] == "toy_matmul" \
        and ops["fusion.1"]["unit"] == "GDMixer"
    assert [ops[f"fusion.{n}"]["phase"] for n in (2, 3)] \
        == ["forward", "forward"]
    # … and undeclared, the same text reads by unit alone
    ops = scopes.attribute(PRODUCTS.replace("project", "toy_matmul"),
                           tuple(unit[:4] for unit in units))
    assert ops["fusion.1"]["unit"] is None
    assert {ops[f"fusion.{n}"]["phase"] for n in (2, 3)} == {"forward"}


def test_the_outermost_scope_names_the_unit():
    names = ["Attn", "GDAttn", "update"]
    assert scopes.scope_of(
        "jit(p)/GDAttn/transpose(jvp(Attn/rope))/mul", names) \
        == (1, False, False)
    assert scopes.scope_of("jit(p)/while/body/Attn/dot", names) \
        == (0, False, False)
    assert scopes.scope_of("jit(p)/GDAttn/update/sub", names) \
        == (1, True, False)
    assert scopes.scope_of(
        "jit(p)/GDAttn/update/fingerprint/gather", names) \
        == (1, True, True)
    assert scopes.scope_of("jit(p)/while", names) is None


# ----------------------------------------------------------------------
# the other programs of a region
# ----------------------------------------------------------------------
def test_chunk_and_accum_programs_keep_their_units():
    wf = conv_dense("scopes_chunk")
    wf.run_chunked(16)
    region = wf._region_unit.region
    chunk = only_program(f"znicz_chunk16__{region.name}")
    assert seen_units(chunk) == {u.name for u in region.units}
    assert "update" in phases_of(chunk, wf.gds[0].name)

    scopes.forget()
    root.common.engine.grad_accum = 4
    wf = conv_dense("scopes_accum")
    wf.run_accumulated()
    region = wf._region_unit.region
    accum = only_program(f"znicz_accum4__{region.name}")
    assert seen_units(accum) == {u.name for u in region.units}
    assert {"backward", "update", "fingerprint"} \
        <= phases_of(accum, wf.gds[0].name)


def test_train_and_eval_variants_are_two_programs():
    rng = np.random.default_rng(0)
    wf = conv_dense(
        "scopes_variants",
        valid_data=rng.normal(size=(8, 8, 8, 3)).astype(np.float32),
        valid_labels=rng.integers(0, 4, 8).astype(np.int32))
    wf.run()
    region = wf._region_unit.region
    found = observe.op_scopes()
    name = f"znicz_step__{region.name}"
    assert set(found) == {name, f"{name}#2"}
    backward = {u.name for u in wf.gds}
    with_gd = [n for n, ops in found.items()
               if seen_units(ops) & backward]
    assert len(with_gd) == 1      # the eval variant skips every GD


# ----------------------------------------------------------------------
# what asking costs, and what not asking costs
# ----------------------------------------------------------------------
def test_asking_compiles_nothing_and_is_memoised():
    built = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _secs, **kw: built.append(event)
        if event.endswith("backend_compile_duration") else None)
    wf = conv_dense("scopes_cost", epochs=2)
    wf.run()
    region = wf._region_unit.region
    compiles = obs_metrics.xla_compiles(f"region:{region.name}")
    spans = observe.TRACER.mark()
    before = (compiles.value, len(built), len(region._cache))
    first = observe.op_scopes()
    assert first and all(first.values())
    assert (compiles.value, len(built), len(region._cache)) == before
    # asking traces and lowers the program for its text (JAX's own
    # stamps say so), and makes none
    assert {ev["name"] for ev in observe.TRACER.to_chrome_trace(
        since=spans)["traceEvents"] if ev.get("cat") == "compile"} \
        <= {"jax:trace", "jax:lower"}
    again = observe.op_scopes()
    assert all(again[name] is first[name] for name in first)
    # the text's thunk went with its first reading
    assert all(p.text is None for p in scopes._PROGRAMS.values())
    # and the region goes on where it was: no tracer left in a Vector
    wf.decision.max_epochs = 3
    wf.decision.complete.value = False
    wf.run()
    assert (compiles.value, len(built)) == before[:2]


def test_a_retrace_puts_the_vectors_back():
    wf = conv_dense("scopes_retrace", epochs=2)
    wf.run()
    region = wf._region_unit.region
    leaves = [vec._devmem for vec in region._vectors]
    jax.clear_caches()      # JAX forgets the lowering: the body re-runs
    assert only_program("znicz_step__")
    assert all(vec._devmem is leaf and not vec._tracing
               for vec, leaf in zip(region._vectors, leaves))


def test_the_map_outlives_the_workflow_and_pins_none_of_it():
    wf = conv_dense("scopes_gone")
    wf.run()
    name = f"znicz_step__{wf._region_unit.region.name}"
    alive = weakref.ref(wf._region_unit.region)
    del wf
    gc.collect()
    assert alive() is None      # a record holds no unit, no Vector
    assert observe.op_scopes()[name]


def test_the_scopes_do_not_hang_on_telemetry():
    root.common.engine.telemetry = False
    wf = conv_dense("scopes_quiet")
    wf.run()
    ops = only_program("znicz_step__")
    assert seen_units(ops) == {u.name
                               for u in wf._region_unit.region.units}


def test_profile_window_writes_the_map(tmp_path, monkeypatch):
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda outdir, profiler_options=None: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    wf = conv_dense("scopes_window")
    with observe.profile_window(str(tmp_path), n_steps=16):
        wf.run()
    with open(tmp_path / "op_scopes.json") as fh:
        written = json.load(fh)
    assert written == observe.op_scopes() and written
    assert (tmp_path / "host_spans.trace.json").exists()
