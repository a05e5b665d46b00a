"""``benchmarks/static_schedule.py`` on a made-up dump: the regions'
spans, the slots a bundle uses, and (PR 41) the MXU's operations by
kind — the counts PERF.md §6 gives for the delta rule's chunk kernels
come through these parsers."""

import importlib.util
import os

import pytest

BUNDLES = """\
 0x10   : > { %1 = vmatpush.msra.mxu0 %v1  ;;  %2 = vmatpush.bf16.xpose.msrb.mxu1 %v2 } /* Start region 7 */
 0x11   : > { %3 = vmatmul.f32.vlgmr.msra.gmra.mxu0 %v3  ;;  %4 = vmatmul.msk.f32.gmra.mxu2 %vm1, %v4 }
 0x12   : > { %5 = vmatmul.bf16.gmra.mxu1 %v5  ;;  %6 = vst [vmem:[#allocation1_spill] sm:$0xff] %v6 }
 0x13   : > { %7 = vmatpush.xpose.msrb.mxu3 %v7 } /* End region 7 */
"""
USED = """\
== CAPACTIY
4 3 4 1 3 3 1 1 2
== UTILIZATION
2 0 0 0 0 0 0 0 0
2 0 1 0 0 0 0 0 0
1 0 0 0 0 0 1 1 0
1 0 0 0 1 0 0 0 0
"""


@pytest.fixture(scope="module")
def schedule():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "static_schedule.py")
    spec = importlib.util.spec_from_file_location("static_schedule", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture
def dump(tmp_path):
    (tmp_path / "1-znicz_kda_chunk_fwd.1-71-final_bundles.txt").write_text(
        BUNDLES)
    (tmp_path / "1-znicz_kda_chunk_fwd.1-69-final_hlo-static-per-bundle-"
     "utilization.txt").write_text(USED)
    return tmp_path


def test_mxu_operations_by_kind(schedule, dump):
    counts = schedule.mxu_operations(
        str(dump / "1-znicz_kda_chunk_fwd.1-71-final_bundles.txt"))
    assert counts == {"vmatpush f32": 2, "vmatpush bf16": 1,
                      "vmatmul f32": 2, "vmatmul bf16": 1}


def test_regions_and_slots(schedule, dump, capsys):
    assert schedule.regions(
        str(dump / "1-znicz_kda_chunk_fwd.1-71-final_bundles.txt")) \
        == {7: (0x10, 0x13)}
    assert schedule.main(str(dump), "znicz_kda_chunk_fwd", 1) == 0
    said = capsys.readouterr().out
    assert "4 bundles" in said and "vmatmul bf16 1" in said
    assert "MXU 6 (38%)" in said and "SPILL 1 (25%)" in said


def test_a_kernel_that_is_not_in_the_dump(schedule, dump, capsys):
    assert schedule.main(str(dump), "znicz_gdr_chunk_bwd") == 1
    assert "no final schedule" in capsys.readouterr().out
