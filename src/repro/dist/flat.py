"""Flat-buffer layout for the fused mixing hot path.

The paper's per-round cost is dominated by moving and folding up to 2L
neighbor models (§III); doing that as a per-leaf tree walk pays every
collective and every accumulate once *per leaf*.  :class:`FlatSpec`
freezes a parameter tree's layout so each client's whole model lives in
**one contiguous lane-padded row**: ``ravel`` turns a ``(B, ...)``-leaf
tree into a single ``(B, N)`` buffer, ``unravel`` restores it exactly.
The fused paths in :mod:`repro.dist.sync` then run a whole mixing round
on that buffer — one ppermute moves one flat row instead of a tree of
leaves, and the accumulate is a :mod:`repro.kernels.weighted_mix`
Pallas kernel streaming tiles through VMEM.

**The flat-buffer contract**

* **Leading batch dim**: every leaf carries the same leading dim B (the
  local-client dim G under ``shard_map``, the population dim C in the
  global view).  Raveling maps leaf ``l`` to columns
  ``offsets[l] : offsets[l] + sizes[l]`` of the (B, N) buffer.
* **Lane padding**: each leaf's segment is zero-padded up to a multiple
  of :data:`repro.kernels.weighted_mix.LANE` (128), so every offset is
  lane-aligned and the total width N is a lane multiple — the kernels
  tile the buffer without re-padding, and per-leaf segments remain
  TPU-sliceable.  Pad columns are mixed like everything else (mixing is
  linear, zeros stay zeros) and dropped by ``unravel``.
* **Dtype-preserving offsets**: the buffer itself is a single floating
  dtype (default f32) and the spec records each leaf's original dtype;
  ``unravel`` casts back, so ``unravel ∘ ravel`` is the exact identity
  for every leaf dtype that embeds losslessly in the buffer dtype
  (bf16/f16/f32 into f32 — params trees).  Wider or non-float leaves
  are rejected loudly rather than rounded silently.

:func:`map_dtype_buffers` is the codec-free mixer's layout: one buffer
per leaf dtype, each in that dtype, so a bf16 model's round holds a
bf16 population.  The resident ``flat_io`` and error-feedback layouts of
:class:`repro.runtime.SlotTrainLoop` still use one f32 buffer.

Specs are pure shape/dtype metadata (hashable, built at trace time from
tracers), so a jitted mixer rebuilds its spec deterministically per
trace and zero-retrace behavior is untouched.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.weighted_mix import LANE


def _pad_to(n: int, lane: int) -> int:
    return -(-n // lane) * lane


@dataclasses.dataclass(frozen=True)
class FlatSpec:
    """Frozen layout of one parameter tree inside a (B, N) flat buffer.

    Built with :meth:`for_tree`; ``ravel``/``unravel`` are exact
    inverses under the module-level contract."""

    treedef: Any
    shapes: Tuple[Tuple[int, ...], ...]     # per-leaf trailing shapes
    dtypes: Tuple[Any, ...]                 # per-leaf original dtypes
    offsets: Tuple[int, ...]                # lane-aligned segment starts
    sizes: Tuple[int, ...]                  # unpadded element counts
    batch: int                              # the shared leading dim B
    size: int                               # N: total padded width
    dtype: Any                              # buffer dtype

    @classmethod
    def for_tree(cls, tree, dtype=jnp.float32, lane: int = LANE) -> "FlatSpec":
        """Freeze the layout of ``tree`` (leaves shaped (B, ...))."""
        leaves, treedef = jax.tree.flatten(tree)
        if not leaves:
            raise ValueError("cannot build a FlatSpec over an empty tree")
        buf_dt = jnp.dtype(dtype)
        if not jnp.issubdtype(buf_dt, jnp.floating):
            raise ValueError(f"buffer dtype must be floating, got {buf_dt}")
        batch = np.shape(leaves[0])[0] if np.ndim(leaves[0]) else None
        shapes, dtypes, offsets, sizes = [], [], [], []
        off = 0
        for i, leaf in enumerate(leaves):
            shape = tuple(np.shape(leaf))
            if not shape or shape[0] != batch:
                raise ValueError(
                    f"leaf {i} shape {shape} does not carry the shared "
                    f"leading batch dim {batch}")
            dt = jnp.dtype(getattr(leaf, "dtype", None)
                           or jnp.result_type(leaf))
            if (not jnp.issubdtype(dt, jnp.floating)
                    or jnp.finfo(dt).bits > jnp.finfo(buf_dt).bits):
                raise ValueError(
                    f"leaf {i} dtype {dt} does not embed losslessly in "
                    f"the {buf_dt} buffer (floating, ≤ {jnp.finfo(buf_dt).bits}"
                    f" bits required)")
            size = int(np.prod(shape[1:], dtype=np.int64)) if shape[1:] else 1
            shapes.append(shape[1:])
            dtypes.append(dt)
            offsets.append(off)
            sizes.append(size)
            off += _pad_to(size, lane)
        return cls(treedef=treedef, shapes=tuple(shapes), dtypes=tuple(dtypes),
                   offsets=tuple(offsets), sizes=tuple(sizes), batch=batch,
                   size=off, dtype=buf_dt)

    def ravel(self, tree) -> jnp.ndarray:
        """Tree of (B, ...) leaves → one contiguous (B, N) buffer."""
        leaves = self.treedef.flatten_up_to(tree)
        parts = []
        for leaf, shape, size, off, nxt in zip(
                leaves, self.shapes, self.sizes, self.offsets,
                self.offsets[1:] + (self.size,)):
            flat = jnp.reshape(leaf, (self.batch, size)).astype(self.dtype)
            pad = (nxt - off) - size
            if pad:
                flat = jnp.pad(flat, ((0, 0), (0, pad)))
            parts.append(flat)
        return jnp.concatenate(parts, axis=1)

    def unravel(self, buf: jnp.ndarray):
        """(B, N) buffer → the original tree, dtypes restored."""
        if buf.shape != (self.batch, self.size):
            raise ValueError(
                f"buffer shape {buf.shape} != ({self.batch}, {self.size})")
        leaves = []
        for shape, dt, off, size in zip(self.shapes, self.dtypes,
                                        self.offsets, self.sizes):
            seg = jax.lax.slice_in_dim(buf, off, off + size, axis=1)
            leaves.append(jnp.reshape(seg, (self.batch,) + shape).astype(dt))
        return self.treedef.unflatten(leaves)

    def unravel_row(self, row: jnp.ndarray):
        """One (N,) flat row → the per-client tree *without* the batch
        dim (leaf l gets shape ``shapes[l]``).  The serving plane's hot
        model-reload seam: a single client's trained weights lift
        straight out of the training loop's flat buffer into a
        ready-to-serve parameter tree — no host round-trip, no re-stack.
        """
        if row.shape != (self.size,):
            raise ValueError(f"row shape {row.shape} != ({self.size},)")
        leaves = []
        for shape, dt, off, size in zip(self.shapes, self.dtypes,
                                        self.offsets, self.sizes):
            seg = jax.lax.slice_in_dim(row, off, off + size, axis=0)
            leaves.append(jnp.reshape(seg, shape).astype(dt))
        return self.treedef.unflatten(leaves)


def map_dtype_buffers(tree, fn: Callable[[jnp.ndarray], jnp.ndarray]):
    """Ravel the leaves of each dtype into one (B, N) buffer of that
    dtype, apply ``fn`` (buffer -> same-shaped buffer) to each, and
    unravel back into a tree shaped like ``tree``."""
    leaves, treedef = jax.tree.flatten(tree)
    groups: Dict[Any, List[int]] = {}
    for i, leaf in enumerate(leaves):
        groups.setdefault(jnp.dtype(leaf.dtype), []).append(i)
    out = list(leaves)
    for dt, idx in groups.items():
        part = [leaves[i] for i in idx]
        spec = FlatSpec.for_tree(part, dtype=dt)
        for i, leaf in zip(idx, spec.unravel(fn(spec.ravel(part)))):
            out[i] = leaf
    return treedef.unflatten(out)
