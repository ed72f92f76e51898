"""chip_smoke.py off the chip: it refuses a CPU, and its serving phase runs
end to end at the smoke config with interpret-mode kernels."""

import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.configs import get_config

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_cpu_with_no_ok_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                          capture_output=True, text=True, env=env,
                          timeout=300, cwd=ROOT)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_prompts_are_seeded_and_half_share_the_prefix(chip_smoke):
    prompts = chip_smoke.make_prompts(512, seed=3)
    assert prompts == chip_smoke.make_prompts(512, seed=3)
    assert len(prompts) == 8
    assert all(256 <= len(p) <= 1024 for p in prompts)
    head = prompts[0][:chip_smoke.SHARED_PREFIX]
    assert [p[:len(head)] == head for p in prompts] == [True, False] * 4


def test_serving_phase_at_smoke_size(chip_smoke, monkeypatch, capsys):
    """The one-chip phase as it runs on the chip, but at the smoke config
    and with the Pallas kernels interpreted: every request finishes, the
    prefix cache hits, STRICT pages are published, and the interpreted
    kernels' logits agree with the ref path."""
    real_compile = chip_smoke.compile_steps

    class Marked:
        """The interpreter emits no tpu_custom_call; mark it present."""

        def __init__(self, compiled):
            self.compiled = compiled

        def __call__(self, *args):
            return self.compiled(*args)

        def as_text(self):
            return "tpu_custom_call"

    def compile_steps(api, params, caches, widths, impl):
        steps, secs = real_compile(api, params, caches, widths,
                                   "interpret" if impl == "pallas" else impl)
        return {w: Marked(c) for w, c in steps.items()}, secs

    monkeypatch.setattr(chip_smoke, "get_config",
                        lambda arch: get_config(arch, smoke=True))
    monkeypatch.setattr(chip_smoke, "compile_steps", compile_steps)
    chip_smoke.one_chip(seed=0)
    out = capsys.readouterr().out
    assert "prefix cache: hits=3" in out
    assert "greedy tokens equal to the ref path" in out
