#!/usr/bin/env python3
"""A Mosaic kernel's STATIC schedule, region by region, from the chip
compiler's own dump — no chip (PERF.md §6, PR 36).

A TPU core issues one VLIW bundle a cycle and the compiler schedules
them ahead of time, so the bundles of a kernel's regions (a ``pl.when``
body, a loop body) say what a visit costs before anything runs.  Make
the dump with any compile for a described v5e::

    LIBTPU_INIT_ARGS="--xla_jf_dump_to=/tmp/llo --xla_jf_dump_llo_text=true" \\
        python3 benchmarks/flash_fwd_probe.py --compile-only lm
    python3 benchmarks/static_schedule.py /tmp/llo znicz_flash_fwd

(the compiling process aborts once the kernel's files are written: the
dumper then looks for a report template this installation lacks).  Per
region: its bundles and, per unit, the slots used and their share of
what the region's bundles offer; for the kernel whole, its MXU
operations by kind (weight loads ``vmatpush`` and row streams
``vmatmul``, f32 or bf16: an f32 product at the highest precision is
six of each per tile, a bf16 one one).  A count, not a time: DMA waits
and a grid step's own prologue are not in it.

The dumper writes ONE kernel's files before it aborts, so give it a
compile whose first Mosaic kernel is the one to read: for the delta
rule's four chunk kernels ``benchmarks/delta_chunk_probe.py
--compile-only --kernel kda_chunk_bwd`` (``gdr_`` / ``kda_``, ``_fwd`` /
``_bwd``) compiles that one alone (PR 41).
"""

from __future__ import annotations

import collections
import glob
import re
import sys

#: columns of ``*-final_hlo-static-per-bundle-utilization.txt`` and the
#: slots a bundle has of each (the file's own ``== CAPACTIY`` block)
UNITS = ("MXU", "XLU", "VALU", "EUP", "VLD", "FILL", "VST", "SPILL", "SALU")
SLOTS = (4, 3, 4, 1, 3, 3, 1, 1, 2)


def regions(path: str) -> dict:
    """``{region: (first bundle, last bundle)}`` of a
    ``*-final_bundles.txt``."""
    first, spans = {}, {}
    address = re.compile(r"^\s*(0x[0-9a-f]+|\d+)\s*:")
    for line in open(path, errors="replace"):
        at = address.match(line)
        if not at:
            continue
        bundle = int(at.group(1), 0)
        for edge, region in re.findall(r"(Start|End) region (\d+)", line):
            if edge == "Start":
                first[int(region)] = bundle
            elif int(region) in first:
                spans[int(region)] = (first[int(region)], bundle)
    return spans


def occupancy(path: str) -> list:
    """One row of slot counts per bundle."""
    rows, reading = [], False
    for line in open(path):
        if line.startswith("== UTILIZATION"):
            reading = True
        elif reading and len(line.split()) == len(UNITS):
            rows.append([int(x) for x in line.split()])
    return rows


def mxu_operations(path: str) -> dict:
    """``{"vmatpush f32": n, "vmatmul bf16": n, …}`` over a
    ``*-final_bundles.txt``."""
    return dict(collections.Counter(
        f"{kind} {'bf16' if '.bf16' in rest else 'f32'}"
        for kind, rest in re.findall(
            r"= (vmatpush|vmatmul)([a-z0-9.]*)",
            open(path, errors="replace").read())))


def main(directory: str, kernel: str, least: int = 300) -> int:
    bundles = glob.glob(f"{directory}/*{kernel}*[0-9]-final_bundles.txt")
    used = glob.glob(f"{directory}/*{kernel}*[0-9]-final_hlo-static-"
                     f"per-bundle-utilization.txt")
    if not bundles or not used:
        print(f"no final schedule of {kernel} under {directory}")
        return 1
    rows = occupancy(used[0])
    print(f"{kernel}: {len(rows)} bundles; " + ", ".join(
        f"{name} {n}" for name, n in sorted(
            mxu_operations(bundles[0]).items())))
    slots = [sum(row[i] for row in rows) for i in range(len(UNITS))]
    print("  whole  " + "  ".join(
        f"{unit} {n} ({n / have / len(rows):.0%})"
        for unit, n, have in zip(UNITS, slots, SLOTS)))
    for region, (lo, hi) in sorted(regions(bundles[0]).items(),
                                   key=lambda item: item[1]):
        if hi - lo < least:
            continue
        slots = [sum(row[i] for row in rows[lo:hi + 1])
                 for i in range(len(UNITS))]
        print(f"  region {region:4d}  {hi - lo:6d} bundles | " + "  ".join(
            f"{unit} {n} ({n / have / (hi - lo):.0%})"
            for unit, n, have in zip(UNITS, slots, SLOTS)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], sys.argv[2], *map(int, sys.argv[3:4])))
