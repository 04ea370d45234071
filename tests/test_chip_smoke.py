"""``chip_smoke.py`` at a tiny size on the CPU.

The script refuses to run without a TPU, so these tests steer its device
check (and its compile-cache switch, which tests never turn on) by
monkeypatching the loaded module.  They run every phase of both forms,
the four-chip one on four virtual CPU devices in a CPU-forced subprocess,
and parse the last-line contract.
"""

import importlib.util
import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SCRIPT = os.path.join(_ROOT, "chip_smoke.py")

TINY = dict(users=240, items=180, ratings=16000, grid=4, kernel_grid=4,
            rank=8, rounds=200, kernel_rounds=2, refit_rounds=5,
            gossip_rounds=10, appends=200, requests=6, max_request=40,
            buckets=(8, 32), k=10)


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", _SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod        # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def _lines(out: str):
    return [json.loads(line) for line in out.strip().splitlines()]


def test_one_chip_phases_at_tiny_size(monkeypatch, capsys):
    cs = _load()
    monkeypatch.setattr(cs, "ML1M", cs.Size(**TINY))
    monkeypatch.setattr(cs, "require_tpu", lambda chips: None)
    monkeypatch.setattr(cs, "enable_compile_cache", lambda: None)
    assert cs.main([]) == 0
    lines = _lines(capsys.readouterr().out)
    assert [ln["phase"] for ln in lines[:-1]] == [
        "ingest", "train", "kernel_fit", "serve_f32", "serve_int8",
        "ingest_while_serving"]
    by = {ln["phase"]: ln for ln in lines[:-1]}
    assert by["train"]["rmse"] < by["train"]["train_mean_rmse"]
    assert by["kernel_fit"]["gradient_path"]["fallbacks"] == 0
    assert by["serve_f32"]["compiles"] == len(TINY["buckets"])
    assert by["serve_int8"]["overlap"] >= 0.99
    dev = jax.devices()[0]
    assert lines[-1] == {"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}


def test_without_a_tpu_exits_nonzero_and_prints_no_result(monkeypatch,
                                                          capsys):
    cs = _load()
    monkeypatch.setattr(cs, "enable_compile_cache", lambda: None)
    assert cs.main([]) != 0
    out = capsys.readouterr()
    assert out.out == ""
    assert "no TPU" in out.err


def test_script_alone_fails(tmp_path):
    shutil.copy(_SCRIPT, tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         capture_output=True, text=True, env=env, timeout=120)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


@pytest.mark.distributed
def test_four_chip_phase_on_virtual_devices():
    prog = f"""
import importlib.util, sys
spec = importlib.util.spec_from_file_location("chip_smoke", {_SCRIPT!r})
cs = sys.modules["chip_smoke"] = importlib.util.module_from_spec(spec)
spec.loader.exec_module(cs)
cs.ML1M = cs.Size(**{TINY!r})
cs.require_tpu = lambda chips: None
cs.enable_compile_cache = lambda: None
sys.exit(cs.main(["--chips", "4"]))
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, env=env, timeout=420)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    lines = _lines(out.stdout)
    assert [ln["phase"] for ln in lines[:-1]] == ["gossip_vs_fullgd",
                                                  "sharded_serving"]
    assert lines[0]["devices_holding"] == {"entries": 4, "U": 4, "W": 4}
    assert lines[-1]["ok"] is True
    assert lines[-1]["device"]["count"] == 4
