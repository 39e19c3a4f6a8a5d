"""Serving drivers: batched greedy decode, and the slot-based
continuous-batching plane.

Two modes, both small-model CPU-runnable demonstrations of the serving
stack the dry-run lowers at production scale:

* ``--mode batch`` (default): prefill a fixed batch of prompts in ONE
  batched forward pass (:func:`repro.models.model.prefill` — the
  teacher-forced one-token-at-a-time loop this replaces cost
  O(prompt_len) dispatches), then decode autoregressively.
* ``--mode slots``: drive :class:`repro.runtime.serving.ServeLoop`
  under a Poisson arrival trace — continuous batching over a
  fixed-capacity request SlotMap with per-slot positions.

  PYTHONPATH=src python -m repro.launch.serve --batch 4 --prompt-len 32 \
      --gen 32 --arch tiny
  PYTHONPATH=src python -m repro.launch.serve --mode slots --capacity 8 \
      --requests 32 --policy continuous

Timing uses ``time.perf_counter`` (monotonic — the repro.obs standard;
wall-clock ``time.time`` can step backwards under NTP and made the old
tok/s numbers untrustworthy), and the decode tok/s denominator counts
every sampled token including the first (the old ``gen - 1`` silently
under-reported throughput).
"""

from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import REGISTRY, reduce_for_smoke
from ..models.model import decode_step, init_cache, init_params, prefill
from .compile_cache import enable_compile_cache
from .train import tiny_lm


def check_tokens(gen_tokens: jnp.ndarray, vocab: int) -> None:
    """Output-validity gate.  A real ``raise`` — the old ``assert``
    vanished under ``python -O``."""
    if bool(jnp.any(gen_tokens < 0)) or bool(jnp.any(gen_tokens >= vocab)):
        raise RuntimeError(
            f"generated tokens escaped the vocab [0, {vocab}): "
            f"min={int(gen_tokens.min())} max={int(gen_tokens.max())}")


def run_batch(cfg, params, args, rng) -> int:
    B = args.batch
    prompts = jnp.asarray(
        rng.integers(0, cfg.vocab_size, size=(B, args.prompt_len)), jnp.int32)
    enc = None
    if cfg.enc_dec:
        enc = jnp.asarray(rng.normal(size=(B, 64, cfg.d_model)), jnp.float32)

    cache_len = args.prompt_len + args.gen
    cache = init_cache(cfg, params, B, cache_len, enc_embeds=enc)

    step = jax.jit(lambda p, c, t: decode_step(cfg, p, c, t))
    prefill_j = jax.jit(lambda p, c, t: prefill(cfg, p, c, t))

    # batched prefill: the whole prompt in one forward pass
    t0 = time.perf_counter()
    logits, cache = prefill_j(params, cache, prompts)
    jax.block_until_ready(logits)
    prefill_s = time.perf_counter() - t0

    # greedy generation; every sampled token counts, including the one
    # drawn from the prefill logits
    t0 = time.perf_counter()
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
    out = [tok]
    for _ in range(args.gen - 1):
        logits, cache = step(params, cache, tok)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        out.append(tok)
    jax.block_until_ready(tok)
    gen_s = time.perf_counter() - t0

    gen_tokens = jnp.concatenate(out, axis=1)
    print(f"prefill: {args.prompt_len} tokens/row in one pass, "
          f"{prefill_s:.3f}s; decode: "
          f"{B * args.gen / max(gen_s, 1e-9):.1f} tok/s")
    print("sample:", np.asarray(gen_tokens[0, :16]).tolist())
    check_tokens(gen_tokens, cfg.vocab_size)
    return 0


def run_slots(cfg, params, args, rng) -> int:
    from ..obs.events import telemetry
    from ..obs.rounds import round_ledger
    from ..runtime.serving import ServeLoop

    with telemetry() as bus, round_ledger() as ledger:
        loop = ServeLoop(cfg, params, capacity=args.capacity,
                         cache_len=args.prompt_len + args.gen,
                         prompt_len=args.prompt_len, policy=args.policy)
        for i in range(args.requests):
            plen = int(rng.integers(1, args.prompt_len + 1))
            loop.submit(rng.integers(0, cfg.vocab_size, plen),
                        max_new=int(rng.integers(1, args.gen + 1)))
        t0 = time.perf_counter()
        done = loop.run()
        wall = time.perf_counter() - t0
    lat = sorted(r.latency_s for r in done)
    toks = sum(len(r.tokens) for r in done)
    for r in done:
        check_tokens(jnp.asarray(r.tokens), cfg.vocab_size)
    print(f"{args.policy}: {len(done)} requests in {wall:.3f}s "
          f"({len(done) / wall:.1f} req/s, {toks / wall:.1f} tok/s), "
          f"p50 {lat[len(lat) // 2] * 1e3:.1f}ms "
          f"p99 {lat[min(len(lat) - 1, int(len(lat) * 0.99))] * 1e3:.1f}ms, "
          f"retraces after warmup: {loop.retraces}")
    print("ledger:", ledger.summary())
    print("counters:", bus.snapshot())
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="tiny",
                    help="'tiny' or any assigned arch id (reduced variant)")
    ap.add_argument("--mode", choices=("batch", "slots"), default="batch")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--capacity", type=int, default=8,
                    help="request slots (slots mode)")
    ap.add_argument("--requests", type=int, default=32,
                    help="trace length (slots mode)")
    ap.add_argument("--policy", choices=("continuous", "static"),
                    default="continuous")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    if args.arch == "tiny":
        cfg = tiny_lm()
    else:
        cfg = reduce_for_smoke(REGISTRY[args.arch])
    print(f"serving {cfg.name}: {cfg.num_layers}L d={cfg.d_model} "
          f"vocab={cfg.vocab_size}")

    key = jax.random.PRNGKey(args.seed)
    params = init_params(cfg, key)
    rng = np.random.default_rng(args.seed)
    if args.mode == "slots":
        return run_slots(cfg, params, args, rng)
    return run_batch(cfg, params, args, rng)


if __name__ == "__main__":
    sys.exit(main())
