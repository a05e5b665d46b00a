"""``moe_held_fit_step_share`` (PR 45): the share of a held layer's
steps whose row buffer ran at its fit size, read from the gauge the
layer sets; its entry in ``BENCHMARK.json`` looked up BY NAME, so that
a later PR's entries do not move it."""

import pytest

from znbench.harness import discovery

NAME = "moe_held_fit_step_share"
BENCH = discovery.load_json(discovery.REPO + "/BENCHMARK.json")


def read():
    return discovery.load_module("layer_metrics", NAME).read(None)


@pytest.fixture
def gauge():
    """``metrics.moe_held`` over a process registry without its family,
    before and after."""
    from znicz_tpu.observe import metrics
    families = metrics.REGISTRY._families
    kept = families.pop("znicz_moe_held", None)
    yield metrics.moe_held
    families.pop("znicz_moe_held", None)
    if kept is not None:
        families["znicz_moe_held"] = kept


def test_the_entry_by_name():
    """A later cell may be appended to its ``workloads``; nothing else
    of it may change."""
    entry, = (m for m in BENCH["per_layer"] if m["name"] == NAME)
    cells = entry.pop("workloads")
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "units",
        "moves": "throughput"}
    # the cells whose expert layers hold a share: the gauge's other reader
    rows, = (m for m in BENCH["per_layer"]
             if m["name"] == "moe_held_rows_per_expert")
    assert cells[:3] == rows["workloads"][:3] == [
        "laguna_train_1of32", "ling_train_1of64", "lfm2_train_1of2"]
    assert entry["moves"] == rows["moves"]


def test_nothing_where_the_program_has_no_such_count(gauge):
    """No layer holds a share: the family was never made.  The parent
    of PR 45: the family has no ``fit_steps``."""
    assert read() is None
    gauge("moe_1", "rows_here").set(8189.0)
    gauge("moe_1", "capacity").set(16384.0)
    assert read() is None


def test_fit_steps_over_steps_mean_over_the_layers(gauge):
    # by hand: 17 of 17 and 8 of 16 -> (100 + 50) / 2
    for unit, fit, steps in (("moe_1", 17.0, 17.0), ("moe_2", 8.0, 16.0)):
        gauge(unit, "fit_steps").set(fit)
        gauge(unit, "steps").set(steps)
    assert read() == pytest.approx(75.0)
    gauge("moe_3", "fit_steps").set(0.0)   # an epoch of no step: left out
    gauge("moe_3", "steps").set(0.0)
    assert read() == pytest.approx(75.0)
