"""Docs gate in tier-1: the docs/ subsystem exists, README links to it,
every relative markdown link resolves, and the public-API doctest examples
execute (the same checks the `docs` CI job runs via tools/check_docs.py)."""
import importlib.util
import pathlib
import re

REPO = pathlib.Path(__file__).resolve().parents[1]


def _load_check_docs():
    spec = importlib.util.spec_from_file_location(
        "check_docs", REPO / "tools" / "check_docs.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_docs_exist_and_linked_from_readme():
    readme = (REPO / "README.md").read_text()
    for doc in ("docs/architecture.md", "docs/paper_map.md",
                "docs/streaming.md", "docs/pipeline.md",
                "docs/serving.md", "docs/kernels.md"):
        assert (REPO / doc).exists(), doc
        assert doc in readme, f"README does not link {doc}"


def test_markdown_links_resolve():
    mod = _load_check_docs()
    assert mod.check_links() == []


def test_public_api_doctests():
    mod = _load_check_docs()
    assert mod.run_doctests() == 0


def test_ci_has_docs_and_streaming_jobs():
    ci = (REPO / ".github" / "workflows" / "ci.yml").read_text()
    assert "tools/check_docs.py" in ci
    assert "cancel-in-progress: true" in ci
    # speed is measured on the chip by bench/run.py; CI runs no benchmark
    assert "benchmarks/" not in ci


def test_ci_tier1_covers_scheduler_ckpt_and_dist():
    ci = (REPO / ".github" / "workflows" / "ci.yml").read_text()
    for module in ("repro.serve.scheduler", "repro.ckpt", "repro.dist"):
        assert f"--cov={module}" in ci, module


def test_ci_dist_lane_and_tests_dist_collectable():
    ci = (REPO / ".github" / "workflows" / "ci.yml").read_text()
    # the dist lane emulates 4 devices and selects only dist-marked tests
    assert "--xla_force_host_platform_device_count=4" in ci
    assert "-m dist" in ci
    # pytest's default norecursedirs hides tests/dist/ — the override that
    # keeps the multi-host suite collectable from the repo root must stay
    toml = (REPO / "pyproject.toml").read_text()
    m = re.search(r"^norecursedirs\s*=\s*(\[.*?\])", toml, re.M)
    assert m, "pyproject must override pytest's default norecursedirs"
    assert '"dist"' not in m.group(1)


def test_scheduler_doctests_are_wired_into_docs_gate():
    mod = _load_check_docs()
    assert "repro.serve.scheduler" in mod.DOCTEST_MODULES
    assert "repro.kernels.tuning" in mod.DOCTEST_MODULES
    assert "repro.dist.multihost" in mod.DOCTEST_MODULES


def test_streaming_doc_covers_scale_out_ingest():
    doc = (REPO / "docs" / "streaming.md").read_text()
    assert "## Scale-out ingest" in doc
    assert "cross_host_merge" in doc
    assert "choose_wire_spec" in doc
    arch = (REPO / "docs" / "architecture.md").read_text()
    assert "streaming.md#scale-out-ingest" in arch
