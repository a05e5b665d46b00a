"""Which unit a device operation belongs to: the step program's own
op → unit / phase map.

A profile names device operations as XLA named them (``fusion.362``).
The knowledge of which unit emitted which HLO instruction is the
program's: ``JitRegion`` traces every member unit under
``jax.named_scope(unit.name)``, the parameter update under ``update``
and the SDC fold inside it under ``fingerprint``, so every instruction
of the COMPILED step program carries
``metadata={op_name="jit(znicz_step__<region>)/<unit>/update/…"}``.
:func:`op_scopes` hands that map out::

    {program name: {HLO instruction name: {
        "unit": "GDMoE_2", "kind": "GDMoE", "family": "MoE",
        "phase": "forward" | "backward" | "update" | "fingerprint"
                 | "pass_sum" | "router_bias" | "route" | "combine"
                 | "project" | "rotate_norm"}}}

- ``kind`` is the unit's class, ``family`` the forward class a
  backward unit is paired with (a forward unit's own pairing class):
  one family holds a layer's forward and backward units;
- forward and backward are told apart by the unit's class, ``update``
  and ``fingerprint`` by the scope: an operation reads ``update`` only
  if EVERY scoped instruction in it lies inside that scope
  (``fingerprint`` is nested in ``update`` and reads likewise; so does
  ``pass_sum``, the sum of a looped span's partial gradients over its
  passes; ``router_bias``, the selection bias's rule; ``route``, an
  expert layer's logits, scores, top k and the sort that plans its
  dispatch, forward and pullback; ``combine``, its experts' rows
  gathered from their tokens and put back, weighted and summed,
  likewise; ``project``, a latent-K/V attention layer's matmuls outside
  its kernels — the fused down-projection, the query's and the K/V's
  up-projections, the out-projection — and ``rotate_norm``, the
  element-wise passes at activation size around them — the pre-norm,
  the latents' norms, the rotations, the scale, the casts to the
  kernels' dtype —, both forward and pullback), else its unit's
  forward / backward.  ``project`` names PRODUCTS, so it alone reads
  by them: a fusion whose every matmul (``convolution`` / ``dot``) lies
  in ``project`` is ``project`` of those matmuls' unit whatever the
  compiler fused around them — the norm's last multiply on the way in,
  a cast or the next norm's sum of squares on the way out, a re-made
  forward row beside a pullback's product: they run in the product's
  loop and their time is the product's; on a TPU hardly one of these
  products stands in a fusion of its own scope alone.  A member of a
  looped
  span traces each application under ``<unit>/pass<r>/``: the pass is
  in the ``op_name`` path, the unit is still the outermost scope;
- a fusion is attributed by ALL the instructions fused into it (the
  fused computation's body in the same text), not by its root alone:
  instructions of more than one unit make it mixed,
  ``{"unit": None, "units": [...], "kinds": [...], "families": [...],
  "phases": [...]}`` (per unit, in the region's order: a weight
  gradient with the forward unit's cast fused in is still one
  family's work, and a reader may say so);
- instructions inside no unit's scope (the scan's ``while``, what the
  compiler added without metadata) are not in the map.

Where the text comes from: a region hands :func:`remember` one record
per program when it first dispatches it (``JitRegion._dispatch``, the
miss path) — a thunk that lowers the SAME jitted function for the same
shapes, dtypes, shardings and donation.  JAX keeps that lowering and
its executable for as long as the jitted function lives, so the thunk
finds them: the text is the running executable's own, nothing is
compiled and nothing is loaded a second time (a program that came from
the repo's persisted store is lowered through JAX's compile cache).
The thunk runs only inside :func:`op_scopes` — lazily, once, off the
dispatch path, never in a process that does not ask — and is dropped
with everything it holds as soon as its text is parsed.  Call it from
the thread that drives the region, between dispatches.

The records are held here, not on the region: whoever asks (a
benchmark's reader, an operator at the end of a run) often asks after
the workflow went out of scope, and a workflow is cyclic garbage the
collector may take at any time.  A record pins no unit and no
``Vector`` — the region's bodies reach their region through a weak
reference — only the jitted function with its executable, and at most
``MAX_PROGRAMS`` of them (the newest win).
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import itertools
import logging
import re
import threading
import time

#: records kept; a process drives a handful of region programs, a test
#: session thousands
MAX_PROGRAMS = 32

_LOG = logging.getLogger("znicz_tpu.observe.scopes")


@dataclasses.dataclass
class Program:
    #: unique in the process: the jitted function's name, ``#<n>`` from
    #: the second program of that name on (a region's train and eval
    #: variants share ``znicz_step__<region>``)
    name: str
    #: per member unit ``(name, kind, family, backward)``
    units: tuple
    #: ``() -> compiled HLO text``; dropped once parsed
    text: object
    scopes: dict | None = None


_PROGRAMS: "collections.OrderedDict[str, Program]" = \
    collections.OrderedDict()
_NAMES: collections.Counter = collections.Counter()
_LOCK = threading.Lock()


def remember(function_name: str, units: tuple, text) -> None:
    """Keep what :func:`op_scopes` needs for one program that was just
    dispatched for the first time."""
    with _LOCK:
        _NAMES[function_name] += 1
        n = _NAMES[function_name]
        name = function_name if n == 1 else f"{function_name}#{n}"
        _PROGRAMS[name] = Program(name, units, text)
        while len(_PROGRAMS) > MAX_PROGRAMS:
            _PROGRAMS.popitem(last=False)


def op_scopes() -> dict:
    """The map of every remembered region program (see the module's
    text), built on the first call that finds the program and kept.  A
    program whose text cannot be had any more (its region is gone AND
    JAX dropped the lowering) is left out."""
    with _LOCK:
        programs = list(_PROGRAMS.values())
    out = {}
    for program in programs:
        if program.scopes is None:
            started = time.perf_counter()
            try:
                text = program.text()
            except Exception as exc:  # noqa: BLE001 — an observer
                _LOG.warning("op_scopes: no text for %s (%s: %s)",
                             program.name, type(exc).__name__, exc)
                continue
            read = time.perf_counter()
            program.scopes = attribute(text, program.units)
            program.text = None
            _LOG.info("op_scopes: %s: %d characters of HLO in %.3f s, "
                      "%d operations attributed in %.3f s",
                      program.name, len(text), read - started,
                      len(program.scopes), time.perf_counter() - read)
        out[program.name] = program.scopes
    return out


def forget() -> None:
    """Drop every record (tests)."""
    with _LOCK:
        _PROGRAMS.clear()
        _NAMES.clear()


# ----------------------------------------------------------------------
# the compiled text
# ----------------------------------------------------------------------
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([^\s=]+)\s+=\s+(.*)$")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
#: the scope ``route`` of an expert layer (``ops/moe.py``) in an
#: ``op_name``: a path element of its own, bare in what the unit traced
#: itself, inside ``jvp(…)`` / ``transpose(jvp(…))`` in a forward traced
#: under ``jax.vjp`` and in that forward's pullback
#: … and its scope ``combine``: the rows of a layer's experts gathered
#: from their tokens and put back, weighted and summed; ``project`` and
#: ``rotate_norm`` of a latent-K/V attention layer (``ops/attention.py``
#: ``_latent_forward``) likewise.  The phases a unit names inside its
#: own scope, in the order ``attribute``'s ``scope`` hands their flags
#: out (a reader asks here whether the program knows a phase)
UNIT_PHASES = ("route", "combine", "project", "rotate_norm")
_ROUTE, _COMBINE, _PROJECT, _ROTATE_NORM = _IN_UNIT = tuple(
    re.compile(rf"(?:^|[/(]){scope}(?:[/)]|$)") for scope in UNIT_PHASES)
#: ``project`` names PRODUCTS: a fusion whose every matmul lies in it
#: is ``project`` of those matmuls' unit, whatever the compiler fused
#: around them (the norm's last multiply on the way in, a cast or the
#: next norm's sum of squares on the way out, a re-made forward row
#: beside a pullback's product, a constant another unit's trace left:
#: they run in the product's loop and their time is the product's);
#: the flag's place in what ``attribute``'s ``scope`` hands out (the
#: unit's index, ``update``, ``fingerprint``, ``pass_sum`` and
#: ``router_bias`` come before the phases of ``UNIT_PHASES``)
_MATMULS = ("convolution", "dot")
_MATMUL_SLOT = 5 + UNIT_PHASES.index("project")
_CALLEES = re.compile(
    r"\b(?:calls|to_apply|body|condition|true_computation|"
    r"false_computation|branch_computations|called_computations)="
    r"(\{[^}]*\}|%?[\w.\-]+)")
#: operations whose called computations run as operations of their own
#: (a trace shows the body's instructions, the caller only contains
#: them): attributed by their own metadata, and followed when looking
#: for what can appear in a trace
_CONTROL = ("while", "conditional", "call")


def _opcode(rest: str) -> str:
    """``rest`` is an instruction after `` = ``: its type (a tuple
    type holds spaces and, on a TPU, tiled layouts hold parentheses),
    then ``opcode(``."""
    start = 0
    if rest.startswith("("):
        depth = 0
        for start, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
    space = rest.find(" ", start)
    return rest[space + 1:rest.find("(", space)]


def parse(text: str) -> tuple[dict, str | None]:
    """``({computation: [(instruction, opcode, op_name, callees)]},
    entry computation)`` of an HLO module's text."""
    computations: dict = {}
    entry = current = None
    for line in text.splitlines():
        if not line:
            continue
        if line[0] not in " }":
            if line.endswith("{") and " -> " in line \
                    and not line.startswith("HloModule"):
                head = line.split("(", 1)[0].split()
                current = computations.setdefault(
                    head[-1].lstrip("%"), [])
                if head[0] == "ENTRY":
                    entry = head[-1].lstrip("%")
            continue
        if line[0] == "}":
            current = None
            continue
        found = _INSTRUCTION.match(line) if current is not None else None
        if found is None:
            continue
        name, rest = found.groups()
        op_name = _OP_NAME.search(rest)
        callees = [c.strip().lstrip("%")
                   for group in _CALLEES.findall(rest)
                   for c in group.strip("{}").split(",") if c.strip()]
        current.append((name, _opcode(rest),
                        op_name.group(1) if op_name else "", callees))
    return computations, entry


def scope_of(op_name: str, names: list) -> tuple | None:
    """``(unit index, in update, in fingerprint)`` of one instruction's
    ``op_name``, or ``None`` outside every unit's scope.  The unit is
    the OUTERMOST scope that is a member's name (a backward unit's ops
    may carry ``transpose(jvp(<forward unit>))`` further in)."""
    path = f"/{op_name}/"
    best = None
    for index, name in enumerate(names):
        at = path.find(f"/{name}/")
        if at >= 0 and (best is None or at < best[0]
                        or (at == best[0]
                            and len(name) > len(names[best[1]]))):
            best = (at, index)
    if best is None:
        return None
    inner = path[best[0] + len(names[best[1]]) + 1:]
    update = inner.find("/update/")
    return (best[1], update >= 0,
            update >= 0 and "/fingerprint/" in inner[update + 7:])


def attribute(text: str, units: tuple) -> dict:
    """The map of one program: see the module's text."""
    computations, entry = parse(text)
    names = [unit[0] for unit in units]
    nested: dict = {}
    @functools.lru_cache(maxsize=None)           # few distinct names
    def scope(op_name: str):
        """:func:`scope_of`, and whether the instruction is one of the
        adds of a looped span's gradient sum (``pass_sum``, a scope
        inside ``update``)."""
        found = scope_of(op_name, names) if op_name else None
        return found and found + (
            found[1] and "/pass_sum/" in f"/{op_name}/",
            "/router_bias/" in f"/{op_name}/",
            *(pattern.search(op_name) is not None
              for pattern in _IN_UNIT))

    def scopes_in(computation: str) -> frozenset:
        """Scopes of every instruction in a computation and in what
        its instructions call."""
        if computation not in nested:
            nested[computation] = frozenset()     # a cycle ends here
            found = set()
            for _name, _opcode_, op_name, callees in \
                    computations.get(computation, ()):
                if scope(op_name) is not None:
                    found.add(scope(op_name))
                for callee in callees:
                    found |= scopes_in(callee)
            nested[computation] = frozenset(found)
        return nested[computation]

    products: dict = {}

    def products_in(computation: str) -> frozenset:
        """Scopes of the matmuls alone, likewise."""
        if computation not in products:
            products[computation] = frozenset()
            found = set()
            for _name, opcode, op_name, callees in \
                    computations.get(computation, ()):
                if opcode in _MATMULS:
                    found.add(scope(op_name))
                for callee in callees:
                    found |= products_in(callee)
            products[computation] = frozenset(found)
        return products[computation]

    out: dict = {}
    seen, queue = set(), [entry] if entry else []
    while queue:
        computation = queue.pop()
        if computation in seen:
            continue
        seen.add(computation)
        for name, opcode, op_name, callees in \
                computations.get(computation, ()):
            scopes = {scope(op_name)} - {None}
            if opcode in _CONTROL or opcode.startswith("async"):
                queue.extend(callees)
            else:
                matmuls = frozenset().union(
                    *(products_in(callee) for callee in callees))
                if matmuls and all(found is not None
                                   and found[_MATMUL_SLOT]
                                   for found in matmuls):
                    scopes = set(matmuls)      # a fusion of `project`
                else:
                    for callee in callees:
                        scopes |= scopes_in(callee)
            if scopes:
                out[name] = _entry(scopes, units)
    return out


def _entry(scopes: set, units: tuple) -> dict:
    by_unit: dict = {}
    for index, *inside in scopes:
        by_unit.setdefault(index, []).append(inside)
    parts = []
    for index in sorted(by_unit):
        name, kind, family, backward = units[index]
        # ``inside``: (update, fingerprint, pass_sum, router_bias,
        # route, combine, project, rotate_norm), as ``attribute``'s
        # ``scope`` hands them out; the scopes nested in ``update``
        # before it
        for phase, slot in (("fingerprint", 1), ("pass_sum", 2),
                            ("update", 0), ("router_bias", 3),
                            *zip(UNIT_PHASES, itertools.count(4))):
            if all(inside[slot] for inside in by_unit[index]):
                break
        else:
            phase = "backward" if backward else "forward"
        parts.append({"unit": name, "kind": kind, "family": family,
                      "phase": phase})
    if len(parts) == 1:
        return parts[0]
    return {"unit": None, "units": [p["unit"] for p in parts],
            "kinds": [p["kind"] for p in parts],
            "families": [p["family"] for p in parts],
            "phases": [p["phase"] for p in parts]}
