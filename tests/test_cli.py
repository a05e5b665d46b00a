"""CLI + Launcher tests (reference: ``veles <workflow.py> <config.py>``
entry, snapshot resume, emergency checkpoints)."""

import glob
import os

import pytest

from znicz_tpu.__main__ import Main, _apply_root_overrides
from znicz_tpu.launcher import Launcher
from znicz_tpu.utils.config import root

pytestmark = pytest.mark.usefixtures("compile_cache_placed_outside")


def test_root_overrides():
    _apply_root_overrides(["wine.learning_rate=0.125",
                           "root.common.seed=77",
                           "wine.tag=fast"])
    assert root.wine.learning_rate == 0.125
    assert root.common.seed == 77
    assert root.wine.tag == "fast"


def test_list_samples(capsys):
    assert Main().run(["--list-samples"]) == 0
    out = capsys.readouterr().out
    for name in ("wine", "mnist", "cifar", "alexnet"):
        assert name in out


def test_cli_trains_wine_numpy():
    main = Main()
    rc = main.run(["wine", "--backend", "numpy",
                   "--root", "wine.max_epochs=3",
                   "--root", "wine.layers=[6]"])
    assert rc == 0
    wf = main.launcher.workflow
    assert wf.loader.epoch_number + 1 >= 3


def test_cli_config_module_applies():
    main = Main()
    rc = main.run(["wine", "znicz_tpu.models.samples.wine_config",
                   "--backend", "numpy",
                   "--root", "wine.max_epochs=2"])
    assert rc == 0
    # config module set lr=0.5; --root later override clamped epochs
    assert main.launcher.workflow.decision.max_epochs == 2


def test_cli_dump_graph(tmp_path):
    dot = tmp_path / "wf.dot"
    assert Main().run(["wine", "--dump-graph", str(dot)]) == 0
    text = dot.read_text()
    assert "digraph" in text and "start_point" in text


def test_cli_dry_run():
    main = Main()
    assert main.run(["wine", "--backend", "numpy", "--dry-run"]) == 0
    assert main.launcher.workflow.is_initialized
    assert main.launcher.workflow.loader.epoch_number == 0


def test_cli_workflow_by_path(tmp_path):
    wf_file = tmp_path / "tiny.py"
    wf_file.write_text(
        "from znicz_tpu.models.samples.wine import build\n"
        "def run(load, main):\n"
        "    load(build, max_epochs=1)\n"
        "    main()\n")
    main = Main()
    assert main.run([str(wf_file), "--backend", "numpy"]) == 0
    assert main.launcher.workflow.loader.epoch_number + 1 >= 1


def test_snapshot_resume_roundtrip(tmp_path):
    from znicz_tpu.models.samples.wine import build

    launcher = Launcher(backend="numpy")
    wf, loaded = launcher._load(
        build, max_epochs=2,
        snapshotter_config={"prefix": "wine_cli",
                            "directory": str(tmp_path)})
    assert not loaded
    launcher._main()
    snaps = sorted(glob.glob(str(tmp_path / "*.pickle.gz")),
                   key=os.path.getmtime)
    assert snaps, "snapshotter wrote nothing"

    resumed = Launcher(backend="numpy", snapshot=snaps[-1])
    wf2, loaded2 = resumed._load(build, max_epochs=4,
                                 snapshotter_config=None)
    assert loaded2
    resumed._main()
    # resumed run continued counting epochs past the snapshot point
    assert wf2.loader.epoch_number + 1 >= 4


def test_launcher_auto_resume_retries(tmp_path, monkeypatch):
    from znicz_tpu.models.samples.wine import build

    launcher = Launcher(backend="numpy", retries=1)
    wf, _ = launcher._load(build, max_epochs=2)
    calls = {"n": 0}
    real_run = wf.run

    def crash_once():
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("injected crash")
        real_run()

    monkeypatch.setattr(wf, "run", crash_once)
    launcher._main()
    assert calls["n"] == 2


def test_launcher_emergency_snapshot(tmp_path):
    from znicz_tpu.models.samples.wine import build

    root.common.dirs.snapshots = str(tmp_path / "snaps")
    launcher = Launcher(backend="numpy")
    wf, _ = launcher._load(build, max_epochs=1)
    wf.initialize(device=launcher.make_device())
    path = launcher._emergency_snapshot(wf)
    assert path and os.path.exists(path)


def test_listen_master_exclusive():
    with pytest.raises(ValueError):
        Launcher(listen="h:1", master="h:2")
