"""Rehearsal of ``chip_smoke.py`` without the chip: its phases at tiny
widths on the CPU, with the paged decode kernel switched to Pallas
interpret mode by the test (the script has no such option), and its
four-chip phase on four virtual CPU devices, where the kernel runs per
head shard under ``jax.shard_map``."""
import dataclasses
import functools
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _shrink(mod, setattr_):
    """Tiny traffic: 12 prompts of 8-32 tokens, 8 new tokens, 4 slots."""
    for name, value in (("PROMPT_BUCKETS", (8, 16, 32)), ("NEW_TOKENS", 8),
                        ("SLOTS", 4), ("POOL_TOKENS", 64)):
        setattr_(mod, name, value)


def _interpret_kernel(setattr_):
    from repro.kernels.paged_attention import ops as paged_ops
    setattr_(paged_ops, "use_pallas_kernel", lambda: True)
    setattr_(paged_ops, "paged_attention",
             functools.partial(paged_ops.paged_attention, interpret=True))


def _tiny(mod, tp):
    return dataclasses.replace(mod.smoke_config(2, tp=tp).reduced(),
                               tp=tp, remat=False)


def test_chip_smoke_refuses_a_host_without_tpu(capsys):
    mod = _load_chip_smoke()
    assert mod.main([]) == 2
    out = capsys.readouterr()
    assert out.out == "" and "no TPU" in out.err


def test_chip_smoke_one_chip_phases_at_tiny_size(monkeypatch, capsys):
    mod = _load_chip_smoke()
    _shrink(mod, monkeypatch.setattr)
    _interpret_kernel(monkeypatch.setattr)
    monkeypatch.setattr(mod, "memory_stat", lambda dev, name: 0)
    mod.run_one_chip(_tiny(mod, 1), seed=0, check_custom_call=False)
    out = capsys.readouterr().out
    assert "phase c: layer weights in ['pinned_host']" in out
    assert "phase b reference check" in out
    assert "phase c reference check" in out


FOUR_CHIPS = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, sys.argv[1])
import test_chip_smoke as t
mod = t._load_chip_smoke()
t._shrink(mod, setattr)
t._interpret_kernel(setattr)
mod.memory_stat = lambda dev, name: 1
mod.run_four_chips(t._tiny(mod, 4), seed=0)
print("FOUR_CHIPS_OK")
"""


def test_chip_smoke_four_chip_phase_on_virtual_devices():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(ROOT / "src")] + [p for p in [env.get("PYTHONPATH")] if p])
    out = subprocess.run(
        [sys.executable, "-c", FOUR_CHIPS, str(ROOT / "tests")],
        capture_output=True, text=True, timeout=900, env=env)
    assert "FOUR_CHIPS_OK" in out.stdout, \
        out.stdout[-2000:] + out.stderr[-4000:]
    assert "four chips reference check" in out.stdout
