"""Compile a cell's programs for a described TPU v5e, without the chip.

  JAX_PLATFORMS=cpu python chipbench/rehearse.py <workload> [...]

For each workload: the masked local step, the mixer and the reference's
step, each compiled by the TPU compiler for a described ``v5e:2x2``
(one of its chips for a one-chip cell, all four for a four-chip cell).
Prints each program's ``memory_analysis()`` and the mixer's
collectives.  Nothing runs, so nothing here is a time or a result.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def _mem(compiled) -> dict:
    m = compiled.memory_analysis()
    return {k: int(getattr(m, f"{k}_size_in_bytes"))
            for k in ("argument", "output", "temp", "alias")}


def rehearse(name: str, topo) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding

    from chipbench import harness
    from repro.configs import INPUT_SHAPES
    from repro.core.mixing import build_permute_schedule
    from repro.dist.compat import make_mesh
    from repro.dist.sync import global_mixer
    from repro.launch.hlo_stats import collective_stats
    from repro.launch.steps import dfl_train_bundle
    from repro.optim.optimizers import adamw

    cell = harness.load_cell(name)
    t, o = cell.traffic, cell.config["optimizer"]
    C = cell.clients
    if cell.chips == 1:
        rows = one = SingleDeviceSharding(topo.devices[0])
        mesh = make_mesh((1, 1), ("data", "model"), devices=topo.devices[:1])
        per_device = C
    else:
        mesh = make_mesh((cell.chips, 1), ("data", "model"),
                         devices=topo.devices[:cell.chips])
        rows = NamedSharding(mesh, P("data"))
        one = SingleDeviceSharding(topo.devices[0])
        per_device = t["clients_per_chip"]
    cfg = harness.arch_config(cell.config)
    shape = dataclasses.replace(INPUT_SHAPES["train_4k"],
                                global_batch=C * t["batch"],
                                seq_len=t["seq_len"])
    opt = adamw(o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                weight_decay=o["weight_decay"])
    bundle = dfl_train_bundle(cfg, shape, mesh, opt, dtype=jnp.bfloat16,
                              sync="none", masked=True,
                              clients_per_device=per_device)
    fam = harness.family(cell.config, cell.root)
    model = cell.config["model"]
    row = jax.eval_shape(lambda: fam.init(model, jax.random.PRNGKey(0)))

    def place(tree, sharding):
        return jax.tree.map(lambda l: jax.ShapeDtypeStruct(
            l.shape, l.dtype, sharding=sharding), tree)

    params = place(jax.tree.map(lambda l: jax.ShapeDtypeStruct(
        (C,) + l.shape, l.dtype), row), rows)
    opt_state = place(jax.eval_shape(jax.vmap(opt.init), params), rows)
    count_sharding = (one if cell.chips == 1
                      else NamedSharding(mesh, P()))
    opt_state = dataclasses.replace(opt_state, count=jax.ShapeDtypeStruct(
        opt_state.count.shape, opt_state.count.dtype,
        sharding=count_sharding))
    batch = {k: jax.ShapeDtypeStruct((C, t["batch"], t["seq_len"]),
                                     jnp.int32, sharding=rows)
             for k in ("tokens", "labels")}
    mask = jax.ShapeDtypeStruct((C,), jnp.float32, sharding=rows)
    out = {"workload": name}
    step = jax.jit(bundle.step, donate_argnums=(0, 1)).lower(
        params, opt_state, batch, mask).compile()
    out["step"] = _mem(step)
    mixer = jax.jit(global_mixer("fedlay", build_permute_schedule(C, 3),
                                 masked=True, fuse=t["fuse"],
                                 codec=t["codec"])).lower(params, mask)
    mixer = mixer.compile()
    hlo = mixer.as_text()
    out["mixer"] = _mem(mixer)
    out["mixer_collectives"] = collective_stats(hlo).counts
    out["mixer_kernels"] = hlo.count("tpu_custom_call")
    _, ref = harness._ref_fns(
        cell.root, cell.config["family"], json.dumps(model, sort_keys=True),
        json.dumps(o, sort_keys=True), "exact", None)
    rp = place(row, one)
    f32 = place(jax.tree.map(lambda l: jax.ShapeDtypeStruct(
        l.shape, jnp.float32), row), one)
    tok = jax.ShapeDtypeStruct((t["batch"], t["seq_len"]), jnp.int32,
                               sharding=one)
    cnt = jax.ShapeDtypeStruct((), jnp.int32, sharding=one)
    out["reference_step"] = _mem(ref.lower(rp, f32, f32, cnt, tok,
                                           tok).compile())
    return out


def main(argv) -> int:
    import jax
    from jax.experimental import topologies
    import repro.kernels.weighted_mix  # noqa: F401
    sys.modules["repro.kernels.weighted_mix"].resolve_interpret = (
        lambda i: False if i is None else i)
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for name in argv:
        print(json.dumps(rehearse(name, topo)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
