"""CLI entry point: ``python -m znicz_tpu <workflow> [<config>]``.

Rebuilds the reference's console entry (reference:
``veles/__main__.py`` + ``scripts/velescli.py`` — the ``veles
<workflow.py> <config.py>`` command): import the config module (it
mutates the global ``root`` tree), import the workflow module, locate
its ``run(load, main)``, and drive it through a
:class:`~znicz_tpu.launcher.Launcher`.

``<workflow>`` may be a file path, a dotted module name, or a bare
sample name (``mnist`` → ``znicz_tpu.models.samples.mnist``).
Config-leaf overrides ride as repeated ``--root key=value`` flags
(reference CLI override behavior), evaluated as Python literals when
possible.
"""

from __future__ import annotations

import argparse
import ast
import importlib
import importlib.util
import os
import sys

from znicz_tpu.launcher import Launcher
from znicz_tpu.utils import prng
from znicz_tpu.utils.config import root
from znicz_tpu.utils.logger import Logger

SAMPLES_PACKAGE = "znicz_tpu.models.samples"


def _import_module(spec: str, kind: str):
    """Import by file path, dotted name, or bare sample name."""
    if os.sep in spec or spec.endswith(".py"):
        path = os.path.abspath(spec)
        if not os.path.exists(path):
            raise FileNotFoundError(f"{kind} file not found: {spec}")
        name = os.path.splitext(os.path.basename(path))[0]
        mod_spec = importlib.util.spec_from_file_location(name, path)
        module = importlib.util.module_from_spec(mod_spec)
        # register BEFORE exec so classes defined in the file pickle
        # against the module actually in sys.modules
        sys.modules[name] = module
        mod_spec.loader.exec_module(module)
        return module
    try:
        return importlib.import_module(spec)
    except ModuleNotFoundError as exc:
        # fall back to the samples package only when the missing module
        # IS the requested one (not a dependency it failed to import)
        if exc.name != spec.split(".")[0] and exc.name != spec:
            raise
    return importlib.import_module(f"{SAMPLES_PACKAGE}.{spec}")


def _apply_root_overrides(pairs: list[str]) -> None:
    for pair in pairs:
        if "=" not in pair:
            raise ValueError(f"--root expects key=value, got '{pair}'")
        key, raw = pair.split("=", 1)
        stripped = raw.strip()
        if stripped.startswith("Tune(") and stripped.endswith(")"):
            # tunable range for --optimize, e.g.
            # --root wine.learning_rate="Tune(0.3, 0.05, 0.8)"
            from znicz_tpu.genetics import Tune
            value = Tune(*ast.literal_eval(stripped[len("Tune"):]))
        else:
            try:
                value = ast.literal_eval(raw)
            except (ValueError, SyntaxError):
                value = raw  # plain string leaf
        node = root
        parts = key.split(".")
        if parts[0] == "root":
            parts = parts[1:]
        for part in parts[:-1]:
            node = getattr(node, part)
        setattr(node, parts[-1], value)


def _list_samples() -> list[str]:
    pkg = importlib.import_module(SAMPLES_PACKAGE)
    out = []
    for entry in sorted(os.listdir(os.path.dirname(pkg.__file__))):
        if entry.endswith(".py") and not entry.startswith("_") \
                and not entry.endswith("_config.py"):
            out.append(entry[:-3])
    return out


def make_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="znicz_tpu",
        description="TPU-native Veles/Znicz: run a workflow "
                    "(reference CLI: `veles <workflow.py> <config.py>`)")
    p.add_argument("workflow", nargs="?",
                   help="workflow .py file, module, or sample name")
    p.add_argument("config", nargs="?",
                   help="config .py file/module mutating the root tree")
    p.add_argument("-s", "--snapshot", help="resume from snapshot file")
    p.add_argument("-b", "--backend", choices=("xla", "tpu", "numpy"),
                   help="device backend (default: root.common.engine."
                        "backend)")
    p.add_argument("-l", "--listen", metavar="HOST:PORT",
                   help="coordinate a multi-host run (process 0; "
                        "reference: master --listen)")
    p.add_argument("-m", "--master", metavar="HOST:PORT",
                   help="join a multi-host run (reference: slave "
                        "--master)")
    p.add_argument("--nodes", type=int, help="total process count")
    p.add_argument("--process-id", type=int, help="this process's index")
    p.add_argument("--retries", type=int, default=0,
                   help="auto-resume attempts after a crash")
    p.add_argument("--seed", type=int, help="override root.common.seed")
    p.add_argument("--root", action="append", default=[],
                   metavar="KEY=VALUE",
                   help="config-leaf override (repeatable), e.g. "
                        "--root mnist.learning_rate=0.01")
    p.add_argument("-v", "--verbose", action="store_true",
                   help="debug-level logging (region compiles, timings)")
    p.add_argument("--no-graphics", action="store_true",
                   help="disable the plotting render thread")
    p.add_argument("--web-status", type=int, metavar="PORT",
                   help="serve the live status dashboard on PORT "
                        "(0 picks a free port)")
    p.add_argument("--web-status-host", default="127.0.0.1",
                   metavar="HOST",
                   help="dashboard bind address (0.0.0.0 to allow "
                        "remote browsers)")
    p.add_argument("--optimize", metavar="GENSxPOP",
                   help="genetic hyperparameter search over Tune "
                        "leaves in the config tree, e.g. "
                        "--optimize 5x8 (reference: veles/genetics)")
    p.add_argument("--chunk", type=int, default=1, metavar="N",
                   help="train N minibatch steps per device dispatch "
                        "(lax.scan over the jit region; amortizes "
                        "dispatch/RPC latency — see "
                        "StandardWorkflow.run_chunked)")
    p.add_argument("--n-model", type=int, default=1, metavar="M",
                   help="model-axis size of the device "
                        "grid (tensor parallelism: layers with "
                        "model_parallel='column'/'row' shard over it; "
                        "standalone runs build the mesh over this "
                        "host's devices)")
    p.add_argument("--dump-graph", metavar="FILE",
                   help="write the workflow's Graphviz DOT and exit")
    p.add_argument("--dry-run", action="store_true",
                   help="build + initialize only; do not train")
    p.add_argument("--list-samples", action="store_true",
                   help="list bundled sample workflows and exit")
    return p


class Main(Logger):
    """The CLI driver (reference: ``veles/__main__.py`` ``Main``)."""

    def run(self, argv: list[str] | None = None) -> int:
        args = make_parser().parse_args(argv)
        import logging

        from znicz_tpu.utils.logger import setup_logging
        setup_logging(logging.DEBUG if args.verbose else logging.INFO)
        if args.list_samples:
            print("\n".join(_list_samples()))
            return 0
        if not args.workflow:
            make_parser().print_usage()
            return 2
        from znicz_tpu.backends import configure_compile_cache
        configure_compile_cache()
        if args.config:
            _import_module(args.config, "config")
        _apply_root_overrides(args.root)
        if args.seed is not None:
            root.common.seed = args.seed
        prng.seed_all(int(root.common.seed))

        module = _import_module(args.workflow, "workflow")
        run_fn = getattr(module, "run", None)
        if run_fn is None:
            self.error("workflow module %s has no run(load, main)",
                       module.__name__)
            return 1

        launcher = Launcher(
            backend=args.backend, snapshot=args.snapshot,
            listen=args.listen, master=args.master,
            n_processes=args.nodes, process_id=args.process_id,
            retries=args.retries,
            graphics=False if args.no_graphics else None,
            web_status=args.web_status,
            web_status_host=args.web_status_host,
            chunk=args.chunk, n_model=args.n_model)
        self.launcher = launcher  # introspection (tests, embedding)
        if args.dump_graph or args.dry_run:
            # build (and initialize) without training
            wf = None

            def fake_main(**kwargs):
                nonlocal wf
                wf = launcher.workflow
                if args.dry_run:
                    wf.initialize(device=launcher.make_device(), **kwargs)
                    if launcher._snapshot_state is not None:
                        # validate the staged snapshot actually applies
                        wf.load_state(launcher._snapshot_state)
                        launcher._snapshot_state = None

            run_fn(launcher._load, fake_main)
            wf = wf or launcher.workflow
            if args.dump_graph:
                dot = wf.generate_graph()
                with open(args.dump_graph, "w") as f:
                    f.write(dot)
                self.info("graph → %s", args.dump_graph)
            return 0
        if args.optimize:
            return self._optimize(args, run_fn)
        try:
            launcher.boot(run_fn)
        except KeyboardInterrupt:
            self.warning("interrupted")
            return 130
        return 0

    def _optimize(self, args, run_fn) -> int:
        """Genetic search: every ``Tune`` leaf in the config tree
        (outside ``root.common``) is a gene; each candidate trains a
        fresh workflow via the sample's own ``run(load, main)``."""
        from znicz_tpu.genetics import (GeneticsOptimizer, apply_genome,
                                        collect_tunes, workflow_fitness)
        gens, _, pop = args.optimize.partition("x")
        generations, population = int(gens), int(pop or 8)
        space = {path: tune
                 for path, tune in collect_tunes(root).items()
                 if not path.startswith("common.")}
        if not space:
            self.error("--optimize given but no Tune leaves in the "
                       "config tree")
            return 1
        self.info("optimizing %d genes: %s", len(space), sorted(space))

        def fitness(genome: dict) -> float:
            # same init/shuffle streams per candidate: scores compare
            # hyperparameters, not seed luck
            prng.seed_all(int(root.common.seed))
            # dotted genes hit the config tree; plain genes ride into
            # the sample's build via the trial launcher
            build_kwargs = apply_genome(genome)
            trial = Launcher(
                backend=args.backend,
                graphics=False if args.no_graphics else None,
                load_kwargs=build_kwargs)
            trial.boot(run_fn)
            return workflow_fitness(trial.workflow)

        opt = GeneticsOptimizer(
            space=space, fitness_fn=fitness, generations=generations,
            population_size=population, seed=int(root.common.seed))
        best = opt.run()
        self.best_genome = best  # introspection
        self.info("best genome (fitness %.4f): %s",
                  opt.best_fitness, best)
        return 0


def main(argv: list[str] | None = None) -> int:
    return Main().run(argv)


if __name__ == "__main__":
    sys.exit(main())
