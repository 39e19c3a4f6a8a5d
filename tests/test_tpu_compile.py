"""Chip-compile tests: the main path's Pallas kernels and programs,
compiled for a described TPU v5e at published widths.

Nothing runs: the TPU compiler, which ships with jax, compiles for a
v5e:2x2 topology that is described, not attached, so these tests catch
what interpret mode cannot — block shapes the Mosaic lowering refuses,
scoped-VMEM overruns, and programs that do not fit 16 GB of HBM.
Kernel widths are those of ``chip_smoke.py``'s trainer: the lane-padded
flat size of ``mamba2-370m`` with C ∈ {2, 4} clients.

The topology is described only inside the module fixture (never at
import): only one process may load the TPU library, and every test
worker imports this file.
"""

import dataclasses
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.configs import REGISTRY
from repro.core.mixing import build_permute_schedule
from repro.dist.flat import FlatSpec
from repro.dist.sync import global_mixer
from repro.kernels.weighted_mix import gather_mix, mix_accumulate, weighted_mix
from repro.kernels.wire_codec import gather_mix_int8, quantize_block
from repro.models import init_params

HBM_BYTES = 15.75e9       # what the v5e compiler grants one program


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    from repro.dist.compat import make_mesh
    return make_mesh((4, 1), ("data", "model"), devices=topo.devices)


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def params_shape():
    return jax.eval_shape(lambda: init_params(
        REGISTRY["mamba2-370m"], jax.random.PRNGKey(0), dtype=jnp.bfloat16))


@pytest.fixture(scope="module")
def flat_n(params_shape):
    """The lane-padded flat width of one mamba2-370m client."""
    return FlatSpec.for_tree(jax.tree.map(
        lambda l: jax.ShapeDtypeStruct((1,) + l.shape, l.dtype),
        params_shape)).size


@pytest.fixture
def compiled_kernels(monkeypatch, topo):
    """Mixers pick interpret mode, and the default layouts their leaf
    kernels read, from the default backend (the CPU here); compile their
    kernels for the described chip, and its layouts, instead."""
    wm = sys.modules["repro.kernels.weighted_mix"]
    monkeypatch.setattr(wm, "resolve_interpret",
                        lambda i: False if i is None else i)
    monkeypatch.setattr(wm, "_layout_device", lambda: topo.devices[0])


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled, compiled.as_text()


def _ring(C):
    return np.stack([np.roll(np.arange(C), -k) for k in range(C)], axis=1)


@pytest.mark.parametrize("C", [2, 4])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_gather_mix_compiles_in_place(one_chip, flat_n, C, dtype):
    buf = jax.ShapeDtypeStruct((C, flat_n), dtype, sharding=one_chip)
    w = jax.ShapeDtypeStruct((C, C), jnp.float32, sharding=one_chip)
    srcs = _ring(C)
    compiled, hlo = _compile(
        lambda b, t: gather_mix(b, srcs, t, interpret=False), buf, w)
    assert "tpu_custom_call" in hlo
    # no padded copy of the population: the width is already lane-aligned
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


@pytest.mark.parametrize("init", [False, True])
def test_mix_accumulate_compiles(one_chip, flat_n, init):
    x = jax.ShapeDtypeStruct((2, flat_n), jnp.float32, sharding=one_chip)
    w = jax.ShapeDtypeStruct((2,), jnp.float32, sharding=one_chip)
    if init:
        _, hlo = _compile(lambda x, w: mix_accumulate(None, x, w,
                                                      interpret=False), x, w)
    else:
        _, hlo = _compile(lambda a, x, w: mix_accumulate(
            a, x, w, interpret=False), x, x, w)
    assert "tpu_custom_call" in hlo


def test_weighted_mix_compiles(one_chip, flat_n):
    x = jax.ShapeDtypeStruct((4, flat_n), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((4,), jnp.float32, sharding=one_chip)
    _, hlo = _compile(lambda x, w, m: weighted_mix(x, w, mask=m,
                                                   interpret=False), x, w, w)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("C", [2, 4])
def test_int8_codec_kernels_compile(one_chip, flat_n, C):
    x = jax.ShapeDtypeStruct((C, flat_n), jnp.float32, sharding=one_chip)
    _, hlo = _compile(lambda x: quantize_block(
        x, with_residual=True, interpret=False), x)
    assert "tpu_custom_call" in hlo
    q = jax.ShapeDtypeStruct((C, flat_n), jnp.int8, sharding=one_chip)
    s = jax.ShapeDtypeStruct((C, flat_n // 128), jnp.bfloat16,
                             sharding=one_chip)
    w = jax.ShapeDtypeStruct((C, C), jnp.float32, sharding=one_chip)
    srcs = _ring(C)
    _, hlo = _compile(lambda q, s, w: gather_mix_int8(
        q, s, srcs, w, interpret=False), q, s, w)
    assert "tpu_custom_call" in hlo


def _stacked(params_shape, C, sharding):
    return jax.tree.map(lambda l: jax.ShapeDtypeStruct(
        (C,) + l.shape, l.dtype, sharding=sharding), params_shape)


def test_flat_mixer_fits_beside_the_optimizer_state(
        one_chip, params_shape, compiled_kernels):
    """The trainer's masked flat mixer on two bf16 mamba2-370m clients:
    one kernel per leaf, and with the two clients' f32 AdamW moments
    resident the round still fits the chip."""
    C = 2
    params = _stacked(params_shape, C, one_chip)
    mask = jax.ShapeDtypeStruct((C,), jnp.float32, sharding=one_chip)
    mixer = global_mixer("fedlay", build_permute_schedule(C, 3),
                         masked=True, fuse="flat")
    compiled, hlo = _compile(mixer, params, mask)
    assert hlo.count("tpu_custom_call") == len(jax.tree.leaves(params))
    mem = compiled.memory_analysis()
    n = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params_shape))
    moments = 2 * C * n * 4
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used + moments < 0.95 * HBM_BYTES, used + moments


def _qwen3_4b_l3v8():
    """``qwen3-4b-l3v8``: Qwen3-4B's widths, 3 layers, an eighth of the
    vocabulary (the benchmark's one-chip cut)."""
    return dataclasses.replace(REGISTRY["qwen3-4b"], num_layers=3,
                               vocab_size=18992)


#: Instructions that would move a leaf through HBM outside the kernels
#: (the ravel's concatenate, a relayout's copy); above 2^16 elements
#: each is a leaf of the models here, not the (C, C) matrix.
RELAYOUT = re.compile(
    r"= (\w+)\[([\d,]*)\]\S* (concatenate|dynamic-update-slice|copy)\(")


@pytest.mark.parametrize("C", [2, 4])
@pytest.mark.parametrize("model", ["mamba2-370m", "qwen3-4b-l3v8"])
def test_leafwise_mixer_compiles_without_a_ravel(one_chip, compiled_kernels,
                                                 model, C):
    """The masked codec-free fused mixer at C ≤ LEAF_MIX_MAX_CLIENTS:
    one kernel per leaf, each on the leaf in its default layout — no
    population-sized concatenate, dynamic-update-slice or copy, and
    temporaries under 64 MiB (the raveled buffer alone was ≥ 1.4 GB)."""
    cfg = (REGISTRY[model] if model in REGISTRY else _qwen3_4b_l3v8())
    shape = jax.eval_shape(lambda: init_params(
        cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16))
    params = _stacked(shape, C, one_chip)
    mask = jax.ShapeDtypeStruct((C,), jnp.float32, sharding=one_chip)
    edge = jax.ShapeDtypeStruct((C, 6), jnp.float32, sharding=one_chip)
    mixer = global_mixer("fedlay", build_permute_schedule(C, 3),
                         masked=True, fuse="flat")
    compiled, hlo = _compile(lambda p, m, e: mixer(p, m, edge_mask=e),
                             params, mask, edge)
    assert hlo.count("tpu_custom_call") == len(jax.tree.leaves(params))
    moved = [(op, dims) for _, dims, op in RELAYOUT.findall(hlo)
             if np.prod([int(d) for d in dims.split(",") if d]) > 2 ** 16]
    assert not moved, moved
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2 ** 20


def test_flat_mixer_is_refused_across_chips(four_chips, params_shape,
                                            compiled_kernels):
    """GSPMD cannot partition a Mosaic kernel: the flat global mixer
    does not compile when the client axis spans chips (which is why
    chip_smoke.py's four-chip phase mixes with the tree walk)."""
    C = 4
    rows = NamedSharding(four_chips, P("data"))
    params = jax.tree.map(lambda l: jax.ShapeDtypeStruct(
        (C,) + l.shape, l.dtype, sharding=rows), params_shape)
    mask = jax.ShapeDtypeStruct((C,), jnp.float32, sharding=rows)
    mixer = global_mixer("fedlay", build_permute_schedule(C, 3),
                         masked=True, fuse="flat")
    with pytest.raises(NotImplementedError,
                       match="cannot be automatically partitioned"):
        jax.jit(mixer).lower(params, mask)


def test_tree_mixer_gathers_the_population_across_chips(four_chips,
                                                         params_shape):
    """What the chip's compiler emits for the global tree-walk mixer
    with one client per chip: the permutation takes become all-gathers
    of every leaf's population, not collective-permutes."""
    from repro.launch.hlo_stats import collective_stats
    C = 4
    rows = NamedSharding(four_chips, P("data"))
    params = jax.tree.map(lambda l: jax.ShapeDtypeStruct(
        (C,) + l.shape, l.dtype, sharding=rows), params_shape)
    mask = jax.ShapeDtypeStruct((C,), jnp.float32, sharding=rows)
    mixer = global_mixer("fedlay", build_permute_schedule(C, 3),
                         masked=True)
    compiled, hlo = _compile(mixer, params, mask)
    counts = collective_stats(hlo).counts
    assert counts.get("all-gather", 0) >= len(jax.tree.leaves(params))
    assert "collective-permute" not in counts
    assert compiled.memory_analysis().temp_size_in_bytes < 4e9
