"""The chip smoke script's phases at reduced size on the CPU.

``chip_smoke.py`` runs the paper's memory-system sweep and the coded-KV
server once on a TPU. Here its phase functions run with the same checks:
the sweep at a short trace length, and the server on qwen2.5-3b's reduced
widths, where the Pallas gather runs in the interpreter.
"""
import importlib.util
import os

import jax
import pytest

from repro.configs.base import get_config

ROOT = os.path.join(os.path.dirname(__file__), "..")


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_main_refuses_a_platform_without_tpu(chip_smoke, capsys):
    platform = jax.devices()[0].platform
    assert platform != "tpu"
    assert chip_smoke.main([]) == 2
    out = capsys.readouterr()
    assert out.out == ""                      # no result line
    assert repr(platform) in out.err


def test_memsys_phase_short_length(chip_smoke):
    out = chip_smoke.phase_memsys(length=64)
    assert out["points"] == 16
    assert out["checks"]["oracle_equal"] == {"uncoded@1.0": True,
                                             "scheme_i@1.0": True}
    assert all(out["checks"]["coded_below_uncoded_at_alpha_1"].values())
    assert out["ok"]


def test_serve_phase_reduced_widths(chip_smoke):
    cfg = get_config("qwen2.5-3b").reduced()
    out = chip_smoke.phase_serve(cfg)
    assert out["checks"]["all_answered"]
    assert out["checks"]["tokens_identical"]
    assert out["checks"]["tokens_in_vocab"]
    assert out["checks"]["gather_bit_exact"]
    assert out["checks"]["parity_consistent"]
    assert out["checks"]["pool_written"]
    # interpreted off the TPU: no compiled kernel in the decode step
    assert out["checks"]["pallas_native"] is False
    assert all(r["answered"] == 8 for r in out["runs"].values())
    assert out["ok"]
