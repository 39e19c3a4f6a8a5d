"""chip_smoke.py's phases at reduce_for_smoke sizes on the CPU, and its
refusal to run (or report success) anywhere but a TPU."""

import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.configs import REGISTRY, reduce_for_smoke

_PATH = os.path.join(os.path.dirname(__file__), "..", "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def test_trainer_phase_runs_the_gossip_path(smoke, capsys):
    cfg = reduce_for_smoke(REGISTRY[smoke.TRAIN_CONFIG])
    res = smoke.trainer_phase(cfg, clients=2, batch=1, seq=32, steps=3)
    out = capsys.readouterr().out
    assert len(res["losses"]) == 3
    assert res["mix_max_abs"] <= smoke.MIX_TOL
    assert "trainer.retraces_after_first_step=0" in out
    assert f"trainer.config={cfg.name}" in out


def test_serving_phase_completes_every_request(smoke, capsys):
    cfg = reduce_for_smoke(REGISTRY[smoke.SERVE_CONFIG])
    res = smoke.serving_phase(cfg, capacity=2, cache_len=48,
                              prompt_len=16, requests=5, max_new=4)
    out = capsys.readouterr().out
    assert res["completed"] == 5 and res["tokens"] >= 5
    assert "serving.retraces_after_warmup=0" in out


def test_four_chip_phase_matches_dense_mixing(smoke, capsys, multi_device):
    cfg = reduce_for_smoke(REGISTRY[smoke.TRAIN_CONFIG])
    res = smoke.four_chip_phase(cfg, devices=jax.devices()[:4], batch=1,
                                seq=32, steps=2, dtype=jnp.float32)
    out = capsys.readouterr().out
    assert res["mix_max_abs"] <= smoke.MIX_TOL_F32
    assert "four_chip.param_devices=4" in out
    collectives = json.loads(
        out.split("four_chip.mixer_collectives=")[1].split()[0])
    assert collectives, "the cross-device mixer holds no collective"


def test_main_refuses_a_non_tpu_platform(smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main([]) != 0
    captured = capsys.readouterr()
    assert '"ok"' not in captured.out
    assert "needs a TPU" in captured.err
