"""bench/run.py refuses to measure without the chip it needs."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "starcoder2-7b.code_complete", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_exits_nonzero_without_a_tpu():
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_rate_is_the_mix_files_not_an_option():
    """The offered load is fixed in the cell's mix file: run.py takes no
    option that changes it."""
    from bench import run
    for opt in ("--rate-scale", "--rate"):
        try:
            run.parse(["--workload", "x", "--seed", "1", "--seconds", "1",
                       opt, "2"])
        except SystemExit:
            continue
        raise AssertionError(f"run.py accepts {opt}")


def test_every_cell_loads_its_files():
    sys.path.insert(0, str(ROOT))
    from bench import harness
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.per_layer:
            assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").exists()
        assert "max_logit_gap" in cell.limits
