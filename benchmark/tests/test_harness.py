"""The harness finds cells, configurations, mixes and metrics by name, and
refuses to run without the chip or without the program."""

import json
import os
import subprocess
import sys

from benchmark import run
from benchmark.tests import tiny

NEW_METRIC = '''"""A metric added as a new file."""


def read(ctx):
    return 42.0
'''


def test_new_files_are_found_without_editing_old_ones(tmp_path):
    before = {p: p.read_bytes() for p in (tiny.REPO / "benchmark").rglob("*")
              if p.is_file() and "__pycache__" not in p.parts}
    root = tiny.make_root(tmp_path, [tiny.TINY_GQA], {"new-mix": tiny.TINY_CLOSED},
                          metrics={"new_metric.test": NEW_METRIC})
    # every file the checkout had is unchanged; the new ones are extra
    for p, data in before.items():
        rel = p.relative_to(tiny.REPO)
        assert (root / rel).read_bytes() == data, rel
    cell = run.find_cell(root, "tiny-gqa.new-mix")
    assert cell.config["name"] == "tiny-gqa" and cell.mix["loop"] == "closed"
    res = run.run(["--workload", "tiny-gqa.new-mix", "--seed", str(2**31 + 11),
                   "--seconds", "1", "--trace", "1"], require_tpu=False, root=root)
    assert res["metrics"]["new_metric.test"] == {"value": 42.0, "unit": "%"}
    assert res["correct"] is True
    assert list(res)[-1] == "checks"
    assert set(res["checks"]["mean_logit_gap"]) == {"value", "limit"}


def test_reference_is_found_by_the_name_the_configuration_gives(tmp_path):
    """A configuration of a new family names a new reference file, and the
    run loads that file from the checkout, not the benchmark's own."""
    cfg = dict(tiny.TINY_GQA, name="tiny-new-family", reference="new_family")
    root = tiny.make_root(tmp_path, [cfg], {"closed": tiny.TINY_CLOSED})
    src = (root / "benchmark" / "reference" / "moe_transformer.py").read_text()
    new = root / "benchmark" / "reference" / "new_family.py"
    new.write_text(src + "\nFOUND_BY_NAME = True\n")
    assert run.reference_module(root, cfg).FOUND_BY_NAME
    res = run.run(["--workload", "tiny-new-family.closed", "--seed", "17", "--seconds", "1",
                   "--trace", "0"], require_tpu=False, root=root)
    assert res["correct"] is True
    missing = dict(cfg, reference="no_such_family")
    try:
        run.reference_module(root, missing)
    except SystemExit as e:
        assert "no_such_family" in str(e)
    else:
        raise AssertionError("a missing reference file must fail the run")


def _cli(root, *extra, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", "tiny-gqa.closed",
         "--seed", "3", "--seconds", "1", "--trace", "0", *extra],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


def _has_result(stdout):
    for line in stdout.splitlines():
        try:
            json.loads(line)
            return True
        except ValueError:
            pass
    return False


def test_cpu_backend_exits_nonzero_without_result(tmp_path):
    root = tiny.make_root(tmp_path, [tiny.TINY_GQA], {"closed": tiny.TINY_CLOSED})
    p = _cli(root)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not _has_result(p.stdout)


def test_benchmark_alone_exits_nonzero_without_result(tmp_path):
    root = tiny.make_root(tmp_path, [tiny.TINY_GQA], {"closed": tiny.TINY_CLOSED})
    os.unlink(root / "src")
    p = _cli(root)
    assert p.returncode != 0
    assert not _has_result(p.stdout)
