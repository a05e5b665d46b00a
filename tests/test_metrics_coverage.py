"""Anti-rot linter: every canonical series constructor in
``observe/metrics.py`` must be exercised somewhere.

A metric family that nothing scrapes or asserts rots silently — it
gets renamed, its labels drift, and the dashboards reading it go
blank with no test failing.  The linter AST-walks ``metrics.py`` for
module-level constructor functions (anything registering a
``znicz_*`` family) and requires each to be either called by name or
have its family name asserted in the exercise corpus: ``tests/``,
``benchmarks/`` and the ``__graft_entry__.py`` dryrun attestations.

The companion self-scrape test closes the loop for the long tail of
families whose production call sites run on paths the tier-1 suite
does not reach (fleet scale events, loader restarts, warmup): it
exercises each canonical constructor and asserts the family renders
in the Prometheus exposition with its HELP/TYPE header — so a rename
or label drift on ANY canonical family fails a test, not a
dashboard.
"""

from __future__ import annotations

import ast
import os
import re

from znicz_tpu.observe import metrics as obs_metrics

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _constructors() -> dict:
    """``{function_name: family_name}`` for every module-level
    constructor in metrics.py registering a ``znicz_*`` family."""
    path = os.path.join(_REPO, "znicz_tpu", "observe", "metrics.py")
    with open(path) as fh:
        tree = ast.parse(fh.read())
    out: dict = {}
    for node in tree.body:
        if (not isinstance(node, ast.FunctionDef)
                or node.name.startswith("_")):
            continue
        for sub in ast.walk(node):
            if (isinstance(sub, ast.Call)
                    and isinstance(sub.func, ast.Attribute)
                    and sub.func.attr in ("counter", "gauge",
                                          "histogram")
                    and sub.args
                    and isinstance(sub.args[0], ast.Constant)
                    and isinstance(sub.args[0].value, str)
                    and sub.args[0].value.startswith("znicz_")):
                out[node.name] = sub.args[0].value
                break
    return out


def _corpus() -> str:
    chunks = []
    for base in ("tests", "benchmarks"):
        directory = os.path.join(_REPO, base)
        for name in sorted(os.listdir(directory)):
            if name.endswith(".py"):
                with open(os.path.join(directory, name)) as fh:
                    chunks.append(fh.read())
    with open(os.path.join(_REPO, "__graft_entry__.py")) as fh:
        chunks.append(fh.read())
    return "\n".join(chunks)


def test_every_canonical_constructor_is_exercised():
    ctors = _constructors()
    assert len(ctors) >= 90  # the canon only grows
    corpus = _corpus()
    uncovered = [
        (name, family) for name, family in sorted(ctors.items())
        if not re.search(rf"\b{name}\s*\(", corpus)
        and family not in corpus]
    assert not uncovered, (
        "canonical series with no test/bench/dryrun exercise "
        f"(add an assertion or a self-scrape): {uncovered}")


def test_canonical_families_render_in_exposition():
    """Exercise the constructors the tier-1 suite reaches no other
    way, then self-scrape: each family must render with its header."""
    m = obs_metrics
    touched = [
        m.backend_info("cpu", "test").set(1),
        m.fed_sources("covgang").set(1),
        m.fed_scrape_age_seconds("covgang", "registry:self").set(0.1),
        m.fleet_latency_seconds("cov", "tenant").observe(0.01),
        m.fleet_replicas("cov", "lm").set(2),
        m.fleet_tenant_tokens("cov", "tenant").set(8.0),
        m.fleet_traffic_weight("cov", "lm", "v2").set(0.25),
        m.flash_band("cov_attention", "band_share").set(0.06),
        m.flash_backward("cov_attention", "passes").set(1),
        m.moe_held("cov_moe", "rows_here").set(1280),
        m.moe_gmm_rows("cov_moe", "visited").set(41216),
        m.delta_scan("cov_delta", "padded_share").set(1.78),
        m.loader_pipeline_restarts("cov").inc(),
        m.moe_aux_loss("cov_moe", "z").set(17.3),
        m.moe_expert_tokens("cov_moe", "max").set(1124),
        m.phase_p99_seconds("cov#0", "decode").set(0.002),
        m.prefix_tokens("cov#0", "hit").inc(4),
        m.serving_bucket_batches("cov#0", 128).inc(),
        m.serving_bucket_rows("cov#0", 128).inc(4),
        m.serving_queue_rows("cov#0").set(3),
        m.serving_warmup_seconds("cov#0").set(1.5),
        m.snapshot_seconds("save").observe(0.2),
        m.trace_requests("cov#0", "ok").inc(),
    ]
    assert touched
    text = m.REGISTRY.to_prometheus()
    for family in _constructors().values():
        fam = m.REGISTRY.get(family)
        if fam is None:
            continue  # not constructed in this process: linter's job
        assert f"# TYPE {family}" in text, family
