"""Chip smoke test: the gossip trainer and the server at published widths.

  python chip_smoke.py              # one TPU: trainer phase, serving phase
  python chip_smoke.py --chips 4    # four TPUs: the cross-chip mixing phase

Runs the system's main paths once through their normal entry points, in
one process, with random weights made from ``--seed``:

* trainer — :class:`repro.overlay.OverlayController` over an NDMP
  simulator drives :class:`repro.runtime.SlotTrainLoop` with the masked
  ``dfl_train_bundle`` step of ``mamba2-370m`` (2 clients on one chip,
  flat Pallas mixer), then mixes the trained population once more with
  the flat kernel and with the plain jnp tree walk and compares them;
* serving — :class:`repro.runtime.ServeLoop` serves seeded requests from
  ``llama3.2-3b``;
* four chips (``--chips 4``, nothing else) — the trainer with one client
  per chip on a 4-device client mesh, its mixer checked against a dense
  mixing matrix built from the schedule, and the collectives the chip's
  compiler put in the mixer and the step.

Each phase prints ``phase.key=value`` lines; any failure raises and
exits nonzero.  The last line is one JSON object naming the device.  On
anything but a TPU the script exits nonzero before any work.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import REGISTRY  # noqa: E402
from repro.core.mixing import masked_mixing_matrix  # noqa: E402
from repro.core.ndmp import Simulator  # noqa: E402
from repro.data.tokens import TokenStream  # noqa: E402
from repro.dist.compat import make_mesh  # noqa: E402
from repro.dist.sync import global_mixer  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.hlo_stats import collective_stats  # noqa: E402
from repro.launch.serve import check_tokens  # noqa: E402
from repro.launch.steps import dfl_train_bundle  # noqa: E402
from repro.models import init_params  # noqa: E402
from repro.models.config import INPUT_SHAPES  # noqa: E402
from repro.optim.optimizers import adamw  # noqa: E402
from repro.overlay import OverlayController  # noqa: E402
from repro.runtime import ServeLoop, SlotTrainLoop, counting_jit  # noqa: E402
from repro.runtime.loop import build_rows  # noqa: E402

TRAIN_CONFIG = "mamba2-370m"
SERVE_CONFIG = "llama3.2-3b"
#: bf16 mixing tolerance: half a bf16 ulp of the largest |param| (< 4)
MIX_TOL = 1e-2
#: f32 mixing tolerance: f32 rows are mixed at f32 precision
MIX_TOL_F32 = 1e-5


def log(phase: str, **kv) -> None:
    print(" ".join(f"{phase}.{k}={v}" for k, v in kv.items()), flush=True)


class CompileClock:
    """Sums JAX's backend-compile seconds inside a ``with`` block."""

    def __enter__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event.endswith("backend_compile_duration"):
            self.seconds += duration


def memory(device, key: str = "peak_bytes_in_use") -> object:
    stats = device.memory_stats()
    return stats.get(key, "not reported") if stats else "not reported"


def param_count(cfg) -> int:
    shapes = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0)))
    return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(shapes))


def _simulator(n: int, seed: int) -> Simulator:
    sim = Simulator(num_spaces=3, latency=0.05, heartbeat_period=0.5,
                    probe_period=1.0, seed=seed)
    sim.seed_network(list(range(n)))
    return sim


def _max_abs_diff(a, b, dtype=None) -> float:
    """Max |a - b| over the leaves (of ``a``'s ``dtype`` when given)."""
    return float(max((jnp.max(jnp.abs(x.astype(jnp.float32)
                                      - y.astype(jnp.float32)))
                      for x, y in zip(jax.tree.leaves(a),
                                      jax.tree.leaves(b))
                      if dtype is None or x.dtype == dtype), default=0.0))


def place_rows(loop: SlotTrainLoop, x):
    """Put a capacity-stacked array on the loop's client axis (a no-op
    without a mesh)."""
    if loop.mesh is None:
        return x
    return jax.device_put(x, NamedSharding(loop.mesh, P(loop.client_axis)))


def build_trainer(cfg, *, clients: int, batch: int, seq: int, seed: int,
                  dtype=jnp.bfloat16, devices=None,
                  fuse="flat") -> SlotTrainLoop:
    """The gossip-training main path: NDMP-driven overlay controller +
    slot train loop + the masked ``dfl_train_bundle`` step.  ``devices``
    (a list) spreads one client per device over a client mesh; None
    keeps every client on the default device."""
    if devices is None:
        mesh, loop_mesh, per_device = make_mesh((1, 1), ("data", "model")), \
            None, clients
    else:
        mesh = make_mesh((len(devices), 1), ("data", "model"),
                         devices=devices)
        loop_mesh, per_device = mesh, clients // len(devices)
    shape = dataclasses.replace(INPUT_SHAPES["train_4k"],
                                global_batch=clients * batch, seq_len=seq)
    opt = adamw(1e-4)
    bundle = dfl_train_bundle(cfg, shape, mesh, opt, dtype=dtype,
                              sync="none", masked=True,
                              clients_per_device=per_device)
    # params and optimizer state are donated: the step rewrites them in
    # place, so the population exists once in HBM
    step, traces = counting_jit(bundle.step, donate_argnums=(0, 1))
    init = jax.jit(functools.partial(init_params, cfg, dtype=dtype))
    key = jax.random.PRNGKey(seed)
    streams = {}

    def make_params(node):
        return init(jax.random.fold_in(key, node))

    def make_batch(node_ids, _step):
        rows = []
        for u in node_ids:
            if u not in streams:
                streams[u] = iter(TokenStream(cfg.vocab_size, batch, seq,
                                              seed=seed, client=u))
            rows.append(next(streams[u]))
        toks, labels = zip(*rows)
        return {"tokens": jnp.asarray(np.stack(toks)),
                "labels": jnp.asarray(np.stack(labels))}

    ctl = OverlayController(_simulator(clients, seed), capacity=clients,
                            fuse=fuse, clients_per_device=per_device)
    return SlotTrainLoop(ctl, local_step=step, make_params=make_params,
                         optimizer=opt, make_batch=make_batch,
                         jit_local_step=False, trace_count=traces,
                         mesh=loop_mesh)


def train(loop: SlotTrainLoop, *, cfg, batch: int, seq: int, steps: int,
          phase: str) -> list:
    """Run the loop; print config, losses, compile time, retraces."""
    clients = loop.capacity
    log(phase, config=cfg.name, params=param_count(cfg),
        layers=cfg.num_layers, d_model=cfg.d_model, clients=clients,
        tokens_per_step=clients * batch * seq)
    with CompileClock() as clock:
        t0 = time.perf_counter()
        recs = loop.run(1)
        first = time.perf_counter() - t0
    traces_after_first = loop.trace_count.traces
    recs = loop.run(steps - 1)
    losses = [r.loss for r in recs]
    log(phase, compile_s=round(clock.seconds, 3),
        first_step_s=round(first, 3), steps=len(losses),
        losses=",".join(f"{x:.6f}" for x in losses),
        retraces_after_first_step=loop.trace_count.traces
        - traces_after_first)
    if not all(np.isfinite(losses)):
        raise RuntimeError(f"non-finite training loss: {losses}")
    if loop.trace_count.traces != traces_after_first:
        raise RuntimeError("the local step retraced after the first step")
    if recs[-1].num_alive != clients:
        raise RuntimeError(f"{recs[-1].num_alive} of {clients} alive")
    return losses


def fresh_population(loop: SlotTrainLoop, step_hlo: bool = False):
    """End training and stack every live client's initial model (its
    ``make_params`` row) into the population a mixing round takes.
    Freshly initialized rows differ by the scale of the weights, so a
    wrong mixer cannot pass; the trained rows may instead sit near
    consensus (with uniform overlay weights a round averages them), and
    then any mixer would look right.  With ``step_hlo`` the compiled HLO
    of the loop's local step is returned too.  Returns (params, mask,
    HLO or None)."""
    ctl = loop.controller
    mask = place_rows(loop, jnp.asarray(ctl.alive_mask()))
    hlo = None
    if step_hlo:
        batch = jax.tree.map(lambda x: place_rows(loop, x),
                             loop.make_batch(ctl.alive, -1))
        hlo = loop.local_step.lower(loop.params, loop.opt_state, batch,
                                    mask).compile().as_text()
    loop.params = loop.opt_state = None            # free the trainer's HBM
    params = build_rows(
        lambda i: loop.make_params(ctl.slots.node_at(i)), loop.capacity,
        None if loop.mesh is None
        else NamedSharding(loop.mesh, P(loop.client_axis)))
    spread = _max_abs_diff(params, jax.tree.map(
        lambda l: jnp.broadcast_to(l[:1], l.shape), params))
    log("population", clients=loop.capacity, row_spread_max_abs=spread)
    if not spread > MIX_TOL:
        raise RuntimeError(f"population rows differ by only {spread}")
    return params, mask, hlo


def trainer_phase(cfg, *, clients: int = 2, batch: int = 1,
                  seq: int = 2048, steps: int = 4, seed: int = 0,
                  dtype=jnp.bfloat16) -> dict:
    """One chip: train, then mix the clients' population once with the
    flat Pallas mixer and once with the jnp tree walk."""
    loop = build_trainer(cfg, clients=clients, batch=batch, seq=seq,
                         seed=seed, dtype=dtype)
    losses = train(loop, cfg=cfg, batch=batch, seq=seq, steps=steps,
                   phase="trainer")
    log("trainer", peak_bytes_in_use=memory(jax.devices()[0]))
    ctl = loop.controller
    params, mask, _ = fresh_population(loop)
    hlo = ctl.mixer.lower(params, mask).compile().as_text()
    kernels = hlo.count("tpu_custom_call")
    flat = ctl.mixer(params, mask)
    # the reference walks the tree in f32, so the difference is the
    # kernel's own (its output is rounded to the params' dtype once)
    tree = jax.jit(global_mixer("fedlay", ctl.schedule, masked=True))
    ref = tree(jax.tree.map(lambda l: l.astype(jnp.float32), params),
               mask)
    diff = _max_abs_diff(flat, ref)
    diff32 = _max_abs_diff(flat, ref, jnp.float32)
    log("trainer", mixer_tpu_custom_calls=kernels,
        mix_flat_vs_jnp_max_abs=diff,
        mix_max_abs_bf16_leaves=_max_abs_diff(flat, ref, jnp.bfloat16),
        mix_max_abs_f32_leaves=diff32)
    if kernels < 1 and jax.default_backend() == "tpu":
        raise RuntimeError("the compiled flat mixer holds no Pallas kernel")
    if not (diff <= MIX_TOL and diff32 <= MIX_TOL_F32):
        raise RuntimeError(f"flat mix differs from jnp by {diff} "
                           f"({diff32} on f32 leaves)")
    return {"losses": losses, "mix_max_abs": diff, "kernels": kernels}


def serving_phase(cfg, *, capacity: int = 8, cache_len: int = 2048,
                  prompt_len: int = 512, requests: int = 16,
                  max_new: int = 32, seed: int = 0,
                  dtype=jnp.bfloat16) -> dict:
    """Continuous batching: seeded prompt and output lengths."""
    log("serving", config=cfg.name, params=param_count(cfg),
        layers=cfg.num_layers, d_model=cfg.d_model, capacity=capacity,
        cache_len=cache_len, prompt_len=prompt_len,
        bytes_in_use_at_start=memory(jax.devices()[0], "bytes_in_use"))
    params = jax.jit(functools.partial(init_params, cfg, dtype=dtype))(
        jax.random.PRNGKey(seed))
    weights = memory(jax.devices()[0], "bytes_in_use")
    loop = ServeLoop(cfg, params, capacity=capacity, cache_len=cache_len,
                     prompt_len=prompt_len)
    log("serving", bytes_in_use_weights=weights,
        bytes_in_use_weights_and_cache=memory(jax.devices()[0],
                                              "bytes_in_use"))
    rng = np.random.default_rng(seed)
    for _ in range(requests):
        plen = int(rng.integers(1, prompt_len + 1))
        loop.submit(rng.integers(0, cfg.vocab_size, plen),
                    max_new=int(rng.integers(1, max_new + 1)))
    with CompileClock() as clock:
        t0 = time.perf_counter()
        done = loop.run()
        wall = time.perf_counter() - t0
    for r in done:
        check_tokens(jnp.asarray(r.tokens), cfg.vocab_size)
    tokens = sum(len(r.tokens) for r in done)
    log("serving", requests_completed=len(done), tokens_generated=tokens,
        ticks=loop.tick_index, retraces_after_warmup=loop.retraces,
        compile_s=round(clock.seconds, 3), run_s=round(wall, 3),
        peak_bytes_in_use=memory(jax.devices()[0]))
    if len(done) != requests:
        raise RuntimeError(f"{len(done)} of {requests} requests completed")
    if loop.retraces:
        raise RuntimeError(f"serving retraced {loop.retraces} times")
    return {"completed": len(done), "tokens": tokens}


def four_chip_phase(cfg, *, devices, batch: int = 1, seq: int = 2048,
                    steps: int = 4, seed: int = 0,
                    dtype=jnp.bfloat16) -> dict:
    """One client per chip, every overlay edge across chips; the mixer
    is checked against a dense mixing matrix built from the schedule.

    The mixer is the jnp tree walk: the flat Pallas mixer is a global
    program GSPMD cannot partition (Mosaic kernels are opaque to it), so
    the chip's compiler refuses it on a multi-chip client mesh."""
    loop = build_trainer(cfg, clients=len(devices), batch=batch, seq=seq,
                         seed=seed, dtype=dtype, devices=devices, fuse=None)
    losses = train(loop, cfg=cfg, batch=batch, seq=seq, steps=steps,
                   phase="four_chip")
    leaf = jax.tree.leaves(loop.params)[0]
    log("four_chip", param_sharding=str(leaf.sharding.spec).replace(" ", ""),
        param_devices=len(leaf.sharding.device_set),
        peak_bytes_in_use=",".join(str(memory(d)) for d in devices))
    ctl = loop.controller
    rows = NamedSharding(loop.mesh, P(loop.client_axis))
    params, mask, step_hlo = fresh_population(loop, step_hlo=True)
    mixer_hlo = ctl.mixer.lower(params, mask).compile().as_text()
    # in f32 the tree walk's own bf16 rounding drops out: what is left
    # is whether the cross-chip program moved the right rows
    params = jax.tree.map(lambda l: l.astype(jnp.float32), params)
    mixed = ctl.mixer(params, mask)
    W = jnp.asarray(masked_mixing_matrix(ctl.schedule, ctl.alive_mask()),
                    jnp.float32)
    dense = jax.jit(lambda p: jax.tree.map(
        lambda l: jnp.einsum("ij,j...->i...", W, l,
                             precision=jax.lax.Precision.HIGHEST), p),
        out_shardings=rows)(params)
    diff = _max_abs_diff(mixed, dense)
    log("four_chip",
        mixer_collectives=json.dumps(
            collective_stats(mixer_hlo).counts, sort_keys=True
        ).replace(" ", ""),
        step_collectives=json.dumps(
            collective_stats(step_hlo).counts, sort_keys=True
        ).replace(" ", ""),
        mix_vs_dense_max_abs=diff)
    if not diff <= MIX_TOL_F32:
        raise RuntimeError(f"mix differs from the dense matrix by {diff}")
    return {"losses": losses, "mix_max_abs": diff}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the cross-chip mixing phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, found platform {platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1
    log("device", kind=devices[0].device_kind.replace(" ", "_"),
        count=len(devices), cache=enable_compile_cache())
    if args.chips == 4:
        four_chip_phase(REGISTRY[TRAIN_CONFIG], devices=devices[:4],
                        seed=args.seed)
    else:
        trainer_phase(REGISTRY[TRAIN_CONFIG], seed=args.seed)
        gc.collect()
        serving_phase(REGISTRY[SERVE_CONFIG], seed=args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
