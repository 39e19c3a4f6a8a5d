"""Substrate: optimizers, checkpointing, token pipeline, HLO stats."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt.checkpoint import CheckpointManager, load, save
from repro.data.tokens import TokenStream, input_specs
from repro.launch.hlo_stats import collective_stats
from repro.models.config import INPUT_SHAPES
from repro.configs import REGISTRY
from repro.optim.optimizers import (adamw, apply_updates,
                                    clip_by_global_norm, cosine_schedule,
                                    global_norm, sgd)


# --------------------------------------------------------------------------
# optimizers
# --------------------------------------------------------------------------

def _quadratic_converges(opt, steps=300):
    params = {"w": jnp.asarray([3.0, -2.0]), "b": jnp.asarray(5.0)}
    target = {"w": jnp.asarray([1.0, 1.0]), "b": jnp.asarray(-1.0)}
    state = opt.init(params)
    for _ in range(steps):
        grads = jax.tree.map(lambda p, t: p - t, params, target)
        updates, state = opt.update(grads, state, params)
        params = apply_updates(params, updates)
    err = max(float(jnp.abs(a - b).max())
              for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(target)))
    return err


def test_sgd_converges():
    assert _quadratic_converges(sgd(0.1)) < 1e-3


def test_sgd_momentum_converges():
    assert _quadratic_converges(sgd(0.05, momentum=0.9)) < 1e-3


def test_adamw_converges():
    assert _quadratic_converges(adamw(0.05, weight_decay=0.0)) < 1e-2


def test_clip_by_global_norm():
    grads = {"a": jnp.ones(4) * 10.0}
    clipped, norm = clip_by_global_norm(grads, 1.0)
    assert float(norm) == pytest.approx(20.0)
    assert float(global_norm(clipped)) == pytest.approx(1.0, rel=1e-5)


def test_cosine_schedule_shape():
    sched = cosine_schedule(warmup=10, total=100, floor=0.1)
    assert float(sched(jnp.asarray(5))) == pytest.approx(0.5)
    assert float(sched(jnp.asarray(10))) == pytest.approx(1.0)
    assert float(sched(jnp.asarray(100))) == pytest.approx(0.1, abs=1e-6)


# --------------------------------------------------------------------------
# checkpointing
# --------------------------------------------------------------------------

def test_ckpt_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": {"c": jnp.ones((4,), jnp.bfloat16) * 1.5,
                  "d": (jnp.asarray(3, jnp.int32), jnp.asarray(2.0))},
            "e": [jnp.zeros((2, 2))]}
    path = str(tmp_path / "ck")
    save(path, tree, {"step": 7})
    restored, meta = load(path)
    assert meta["step"] == 7
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_ckpt_manager_retention(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        mgr.save(s, {"x": jnp.asarray(float(s))})
    assert mgr.steps() == [3, 4]
    tree, meta = mgr.restore()
    assert float(tree["x"]) == 4.0 and meta["step"] == 4


# --------------------------------------------------------------------------
# token pipeline
# --------------------------------------------------------------------------

def test_token_stream_deterministic_and_noniid():
    a1 = list(TokenStream(512, 2, 16, seed=0, client=0).batches(2))
    a2 = list(TokenStream(512, 2, 16, seed=0, client=0).batches(2))
    b = list(TokenStream(512, 2, 16, seed=0, client=1).batches(2))
    np.testing.assert_array_equal(a1[0][0], a2[0][0])
    assert not np.array_equal(a1[0][0], b[0][0])   # client shards differ
    x, y = a1[0]
    assert x.shape == (2, 16) and y.shape == (2, 16)
    assert x.min() >= 0 and x.max() < 512


def test_input_specs_all_pairs():
    for arch, cfg in REGISTRY.items():
        for shape in INPUT_SHAPES.values():
            specs = input_specs(cfg, shape)
            if shape.kind == "decode":
                assert specs["token"].shape == (shape.global_batch, 1)
            else:
                assert specs["tokens"].shape == (shape.global_batch,
                                                 shape.seq_len)
                if cfg.enc_dec:
                    assert "enc_embeds" in specs


# --------------------------------------------------------------------------
# HLO collective parsing
# --------------------------------------------------------------------------

FAKE_HLO = """
  %ag = bf16[16,1024]{1,0} all-gather(%x), replica_groups={{0,1,2,3}}, dimensions={0}
  %ar = f32[256]{0} all-reduce(%y), replica_groups=[16,32]<=[512], to_apply=%add
  %rs = f32[64]{0} reduce-scatter(%z), replica_groups={{0,1}}, dimensions={0}
  %cp = bf16[8,8]{1,0} collective-permute(%w), source_target_pairs={{0,1},{1,0}}
  %aa = f32[32]{0} all-to-all(%v), replica_groups={{0,1,2,3,4,5,6,7}}
"""


TPU_HLO = """
  %all-gather.1 = bf16[4,1,1024]{2,1,0:T(2,128)(2,1)} all-gather(%b), channel_id=8, replica_groups=[1,4]<=[4], dimensions={0}
  %all-reduce = f32[4]{0:T(128)S(1)} all-reduce(%d), channel_id=40, replica_groups=[1,4]<=[4], to_apply=%add
  %cp.1 = (bf16[8,128]{1,0:T(8,128)(2,1)}, bf16[8,128]{1,0:T(8,128)(2,1)}) collective-permute-start(%w), source_target_pairs={{0,1},{1,0}}
"""


def test_collective_stats_parse_tpu_layouts():
    """Compiled TPU HLO carries tiled layouts (``{1,0:T(8,128)}``) in
    every shape; the parser still finds each collective once."""
    st = collective_stats(TPU_HLO)
    assert st.counts == {"all-gather": 1, "all-reduce": 1,
                         "collective-permute": 1}
    assert st.result_bytes["all-gather"] == 4 * 1024 * 2


def test_collective_stats_parse():
    st = collective_stats(FAKE_HLO)
    assert st.counts == {"all-gather": 1, "all-reduce": 1,
                         "reduce-scatter": 1, "collective-permute": 1,
                         "all-to-all": 1}
    # all-gather result 16*1024*2 bytes, group 4 → wire (3/4)·32768
    assert st.result_bytes["all-gather"] == 32768
    assert st.wire_bytes_per_device > 0
    # collective-permute is point-to-point: exactly its bytes
    assert st.result_bytes["collective-permute"] == 128
