"""``moe_gmm_overwork`` (PR 34): the grouped-matmul kernels' visited
over real rows, read from the gauge an expert layer sets; its entry in
``BENCHMARK.json`` looked up BY NAME, so that a later PR's entries do
not move it."""

import pytest

from znbench.harness import discovery

NAME = "moe_gmm_overwork"
BENCH = discovery.load_json(discovery.REPO + "/BENCHMARK.json")


def read():
    return discovery.load_module("layer_metrics", NAME).read(None)


@pytest.fixture
def registry():
    """The process registry without this family, before and after."""
    from znicz_tpu.observe import metrics
    families = metrics.REGISTRY._families
    kept = families.pop("znicz_moe_gmm_rows", None)
    yield metrics
    families.pop("znicz_moe_gmm_rows", None)
    if kept is not None:
        families["znicz_moe_gmm_rows"] = kept


def test_the_entry_by_name():
    """A later cell may be appended to its ``workloads``; nothing else
    of it may change."""
    entry, = (m for m in BENCH["per_layer"] if m["name"] == NAME)
    cells = entry.pop("workloads")
    assert entry == {
        "name": NAME, "unit": "ratio", "better": "lower",
        "source": "program_counter", "layer": "kernels",
        "moves": "throughput"}
    assert cells[:2] == ["olmoe_train_t4096", "laguna_train_1of32"]
    assert set(cells) <= {c["name"] for c in BENCH["workloads"]}
    # the layer and the end-to-end metric as the kernels' time has them
    time, = (m for m in BENCH["per_layer"]
             if m["name"] == "moe_gmm_ms_per_step")
    assert entry["moves"] == time["moves"]
    assert set(cells) <= set(time["workloads"])


def test_nothing_where_the_program_has_no_such_gauge(registry):
    """The parent of PR 34: the family was never made."""
    assert read() is None


def test_visited_over_real_mean_over_the_layers(registry):
    gauge = getattr(registry, "moe_gmm_rows", None)
    if gauge is None:
        pytest.skip("a program from before PR 34")
    # by hand: 40,960 of 32,768 and 49,152 of 32,768 -> (1.25 + 1.5) / 2
    for unit, visited in (("moe_1", 40960.0), ("moe_2", 49152.0)):
        gauge(unit, "visited").set(visited)
        gauge(unit, "real").set(32768.0)
    assert read() == pytest.approx(1.375)


def test_a_layer_on_the_xla_path_sets_no_series_and_is_left_out(registry):
    gauge = getattr(registry, "moe_gmm_rows", None)
    if gauge is None:
        pytest.skip("a program from before PR 34")
    gauge("moe_1", "visited").set(3072.0)
    gauge("moe_1", "real").set(1280.0)
    gauge("moe_2", "real").set(0.0)       # never fed: no kernel ran
    assert read() == pytest.approx(2.4)
