"""``chip_smoke.py``'s one-chip phases, run on the CPU at the smoke config:
the same calls into the served path as on the chip, at a small width."""
import importlib.util
import os

import jax
import pytest

from repro.configs import get_smoke_config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("phase", ["check", "serve"])
def test_one_chip_phase_runs_at_smoke_width(phase, capsys):
    smoke = _chip_smoke()
    run = {"check": smoke.phase_check, "serve": smoke.phase_serve}[phase]
    run(get_smoke_config("phi4-mini-3.8b"), jax.devices()[0])
    out = capsys.readouterr().out
    if phase == "check":
        assert "compile xla prefill:" in out and "compile pallas decode:" in out
        assert "decode logits pallas vs xla" in out
    else:
        assert out.count("request ") == smoke.SERVE_REQUESTS
        assert "compiles=" in out
