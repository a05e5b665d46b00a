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
                 | "pass_sum" | <a phase the unit's class declares>}}}

- ``kind`` is the unit's class, ``family`` the forward class a
  backward unit is paired with (a forward unit's own pairing class):
  one family holds a layer's forward and backward units;
- forward and backward are told apart by the unit's class, every other
  phase by a scope inside the unit's own.  This module knows the scopes
  the REGION opens in every unit: ``update`` and, nested in it,
  ``fingerprint`` and ``pass_sum`` (the sum of a looped span's partial
  gradients over its passes).  Any other is DECLARED by the unit that
  opens it — ``PHASES`` on its class (``AcceleratedUnit.PHASES``;
  ``nn_units.phases_of`` gives a backward unit its forward's, whose
  scopes a pullback's operations carry as ``transpose(jvp(<scope>))``):
  the scopes in the order they are tested, each with how an operation
  is read into it.  :data:`ALL`: an operation reads the phase only if
  EVERY scoped instruction in it lies inside that scope (as the
  region's own scopes read).  :data:`PRODUCTS`: a fusion whose every
  matmul (``convolution`` / ``dot``) lies in the scope reads the phase
  of those matmuls' unit whatever the compiler fused around them — a
  norm's last multiply on the way in, a cast on the way out, a re-made
  forward row beside a pullback's product: they run in the product's
  loop and their time is the product's.  What each phase of a unit
  holds is in that unit's docstring; a region hands the declarations
  in with the members (:func:`remember`), and this module names no
  unit and no phase of one.  The region's nested scopes are tested
  first, then the unit's in declared order, else the unit's forward /
  backward.  A member of a looped span traces each application under
  ``<unit>/pass<r>/``: the pass is in the ``op_name`` path, the unit
  is still the outermost scope;
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
    #: per member unit ``(name, kind, family, backward, phases)``,
    #: ``phases`` the ``((scope, how), …)`` its class declares
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
#: how an operation is read into a phase a unit declares (the module's
#: text): by all its scoped instructions, or by its matmuls
ALL, PRODUCTS = "all", "products"
_MATMULS = ("convolution", "dot")
#: the scopes the region opens in every unit: ``update`` and, nested
#: in it, the two tested before it
_NESTED = ("fingerprint", "pass_sum")
#: every phase a unit class of this process declares, in the order the
#: classes were defined (:func:`declare`): a reader asks here whether
#: the program knows a phase
UNIT_PHASES: tuple = ()


def declare(phases: dict) -> None:
    """Take a unit class's ``PHASES`` (``AcceleratedUnit`` hands them
    in as the class is defined): ``{scope: ALL | PRODUCTS}``."""
    global UNIT_PHASES
    for scope, how in phases.items():
        if how not in (ALL, PRODUCTS) or scope in (
                "forward", "backward", "update", *_NESTED):
            raise ValueError(
                f"PHASES: {scope!r}: {how!r} — a scope of the unit's "
                f"own, read by {ALL!r} or by {PRODUCTS!r}")
        if scope not in UNIT_PHASES:
            UNIT_PHASES += (scope,)


@functools.lru_cache(maxsize=None)
def pattern(scope: str) -> "re.Pattern":
    """A scope in an ``op_name``: a path element of its own, bare in
    what the unit traced itself, inside ``jvp(…)`` /
    ``transpose(jvp(…))`` in a forward traced under ``jax.vjp`` and in
    that forward's pullback."""
    return re.compile(rf"(?:^|[/(]){re.escape(scope)}(?:[/)]|$)")


#: pinned by name in znbench/tests/test_smallthinker_cell.py:422, which
#: only a benchmark PR may edit (ROADMAP S0): ask :func:`pattern`
_ROUTE = pattern("route")
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


def _locate(op_name: str, names: list) -> tuple | None:
    """``(unit index, the path inside that unit's scope)``: the unit is
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
    return best[1], path[best[0] + len(names[best[1]]) + 1:]


def _region_scopes(inner: str) -> list:
    """The region's scopes an instruction lies in: ``update`` and
    those of ``_NESTED`` inside it."""
    update = inner.find("/update/")
    if update < 0:
        return []
    return ["update", *(scope for scope in _NESTED
                        if f"/{scope}/" in inner[update + 7:])]


def scope_of(op_name: str, names: list) -> tuple | None:
    """``(unit index, in update, in fingerprint)`` of one instruction's
    ``op_name``, or ``None`` outside every unit's scope."""
    found = _locate(op_name, names)
    if found is None:
        return None
    inside = _region_scopes(found[1])
    return found[0], "update" in inside, "fingerprint" in inside


def attribute(text: str, units: tuple) -> dict:
    """The map of one program: see the module's text.  ``units``: per
    member ``(name, kind, family, backward[, phases])``."""
    computations, entry = parse(text)
    names = [unit[0] for unit in units]
    declared = [dict(unit[4]) if len(unit) > 4 else {} for unit in units]
    by_products = [frozenset(scope for scope, how in phases.items()
                             if how == PRODUCTS) for phases in declared]
    nested: dict = {}

    @functools.lru_cache(maxsize=None)           # few distinct names
    def scope(op_name: str):
        """``(unit index, the scopes the instruction lies in)``: the
        region's and the ones its unit declares."""
        found = _locate(op_name, names) if op_name else None
        if found is None:
            return None
        index, inner = found
        return index, frozenset(_region_scopes(inner)) | {
            name for name in declared[index]
            if pattern(name).search(inner)}

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
                if matmuls and all(
                        found is not None
                        and found[1] & by_products[found[0]]
                        for found in matmuls):
                    scopes = set(matmuls)   # read by its products
                else:
                    for callee in callees:
                        scopes |= scopes_in(callee)
            if scopes:
                out[name] = _entry(scopes, units, declared)
    return out


def _entry(scopes: set, units: tuple, declared: list) -> dict:
    by_unit: dict = {}
    for index, inside in scopes:
        by_unit.setdefault(index, []).append(inside)
    parts = []
    for index in sorted(by_unit):
        name, kind, family, backward = units[index][:4]
        # the scopes nested in ``update`` before it, then the unit's
        # own in the order it declares them
        for phase in (*_NESTED, "update", *declared[index]):
            if all(phase in inside for inside in by_unit[index]):
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
