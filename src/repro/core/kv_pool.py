"""Paged KV block pool: the serve-path analog of the Skip-Cache.

Skip2-LoRA's thesis is cache-to-skip-compute: pay for a forward once,
then reuse its intermediate state instead of recomputing. The adapt path
does it with cached activations; this module does it for *prefill* — a
fixed pool of KV blocks (vLLM-style paged layout) that the request
scheduler's radix prefix index (``core.prefix_index``) maps token
prefixes onto, so an admitted prompt whose prefix is already pooled
copies blocks instead of running the backbone over them.

Layout
------
The device data plane is exactly ``init_serve_caches(cfg, n_blocks,
block)`` — the periods/remainder pytree the whole serve path already
speaks, with the *batch* axis reinterpreted as the block-id axis:
period leaves ``(n_per, n_blocks, block, n_kv, hd)``, remainder leaves
``(n_blocks, block, n_kv, hd)``. Every per-leaf move addresses axis
``-4``, which is the batch/block axis in both layouts, so gather/store
code is layout-agnostic (the same trick the scheduler's admission
scatter uses).

Control plane (host-side, like the AdapterPool's slot table):

  - ``refs[i]``: reference count per block. The radix index holds one
    ref per indexed block; every in-flight admission that reused the
    block holds one more. 0 <=> on the free list.
  - ``free``: LIFO free list (allocation order is deterministic).
  - ``version``: bumped on every data-plane write (publish/copy/reset)
    — anything memoising derived state keys off it.
  - ``generation``: bumped on reset/restore. Block-id handles carry the
    generation they were minted under; stale handles no-op on release
    instead of corrupting a reborn block's refcount.

Copy-on-write rule: pooled blocks are IMMUTABLE while shared. Live rows
decode into private dense cache rows (divergence materialises privately,
so the classic vLLM mid-block COW degenerates to publish-on-retire);
``copy_block`` is the primitive for any future in-pool writer — it
returns the block itself when exclusively held and a fresh copy when
shared, moving the caller's ref.
"""

from __future__ import annotations

from collections import Counter
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import runtime as RT
from repro.kernels.flash_attn import paged

Params = Any

#: Fallback block size (tokens per KV block). ``kernels.autotune``'s
#: ``tune_kv_block`` measures the gather+publish round-trip per candidate
#: and installs the winner here via ``set_default_block`` (resolution at
#: pool construction, like the kernel tile defaults).
DEFAULT_BLOCK = 8

_DEFAULT: dict = {"block": None}


def set_default_block(block: Optional[int]) -> None:
    """Install an autotuned block size as the process-wide default
    (``None`` resets to the untuned ``DEFAULT_BLOCK``)."""
    if block is not None and block < 1:
        raise ValueError(f"kv block {block} must be >= 1")
    _DEFAULT["block"] = block


def get_default_block() -> int:
    return _DEFAULT["block"] or DEFAULT_BLOCK


class KVPoolExhausted(RuntimeError):
    """Allocation failed even after the caller's eviction pass."""


def _leaf_gather(leaf: jax.Array, tables: jax.Array, block: int,
                 use_kernel: bool) -> jax.Array:
    """(..., NB, block, n_kv, hd) + (B, T) ids -> (..., B, T*block, n_kv, hd)."""
    b, t = tables.shape
    if use_kernel:
        if leaf.ndim == 4:
            return paged.gather(leaf, tables, use_kernel=True)
        return jax.vmap(
            lambda p: paged.gather(p, tables, use_kernel=True)
        )(leaf)
    out = jnp.take(leaf, tables.reshape(-1), axis=-4)
    lead = leaf.shape[:-4]
    return out.reshape(lead + (b, t * block) + leaf.shape[-2:])


def gather_blocks(data: Params, tables: jax.Array, *, block: int,
                  use_kernel: bool = False) -> Params:
    """Gather a batch of block tables out of the pool tree: every leaf
    (..., NB, block, n_kv, hd) -> (..., B, T*block, n_kv, hd). Traced —
    call inside the admission jit so the copies fuse with the tail
    prefill. Padded table entries must be valid ids (callers mask the
    padded key positions; see ``attn_prefill_ext``'s garbage doctrine)."""
    return jax.tree.map(
        lambda x: _leaf_gather(x, tables, block, use_kernel), data
    )


class KVBlockPool:
    """One shard's paged KV block pool (device data + host accounting)."""

    def __init__(self, cfg, *, n_blocks: int, block: int, device=None):
        from repro.models.lm import init_serve_caches

        if n_blocks < 1 or block < 1:
            raise ValueError(f"kv pool needs n_blocks, block >= 1; "
                             f"got {n_blocks}, {block}")
        self.cfg = cfg
        self.n_blocks = int(n_blocks)
        self.block = int(block)
        self.device = device
        # Commit the data plane to its device explicitly (never rely on
        # default placement): publish/copy donate and return committed
        # buffers, so an *uncommitted* fresh pool would give the very first
        # publish per geometry a different argument layout than every later
        # one — two compiles of the same program, one of them mid-replay.
        self.data = jax.device_put(
            init_serve_caches(cfg, self.n_blocks, self.block),
            device if device is not None else jax.devices()[0],
        )
        self.refs = np.zeros((self.n_blocks,), np.int32)
        #: LIFO over descending ids so allocation pops block 0 first.
        self.free: list[int] = list(range(self.n_blocks - 1, -1, -1))
        self.version = 0
        self.generation = 0
        self.counters: Counter = Counter()

    # -- accounting ----------------------------------------------------------

    def n_free(self) -> int:
        return len(self.free)

    def alloc(self, n: int) -> list[int]:
        """Pop ``n`` free blocks (ref = 1 each). Raises ``KVPoolExhausted``
        when the free list is short — the caller (prefix index) evicts
        unreferenced radix leaves and retries."""
        if n > len(self.free):
            raise KVPoolExhausted(
                f"kv pool needs {n} blocks, {len(self.free)} free "
                f"of {self.n_blocks}"
            )
        ids = [self.free.pop() for _ in range(n)]
        self.refs[ids] += 1
        self.counters["alloc"] += n
        return ids

    def ref(self, ids) -> None:
        ids = np.asarray(ids, np.int64).reshape(-1)
        if ids.size == 0:
            return
        if np.any(self.refs[ids] <= 0):
            raise RuntimeError(f"ref of unallocated kv block(s) {ids.tolist()}")
        self.refs[ids] += 1

    def deref(self, ids, generation: Optional[int] = None) -> None:
        """Drop one reference per id; blocks hitting zero return to the
        free list. A ``generation`` older than the pool's means the handle
        predates a reset/restore — released silently (the block it named
        no longer exists)."""
        if generation is not None and generation != self.generation:
            self.counters["stale_release"] += 1
            return
        ids = np.asarray(ids, np.int64).reshape(-1)
        if ids.size == 0:
            return
        if np.any(self.refs[ids] <= 0):
            raise RuntimeError(f"deref of free kv block(s) {ids.tolist()}")
        self.refs[ids] -= 1
        freed = [int(i) for i in ids if self.refs[i] == 0]
        self.free.extend(sorted(freed, reverse=True))
        self.counters["freed"] += len(freed)

    def check_no_leaks(self, expected_held: int) -> None:
        """Ref-count invariant: every allocated block holds exactly
        ``refs`` counted references, and free-list + held == n_blocks.
        ``expected_held`` is the number of blocks the radix index (plus
        any in-flight rows) should account for."""
        held = int((self.refs > 0).sum())
        if held + len(self.free) != self.n_blocks:
            raise RuntimeError(
                f"kv pool leak: {held} held + {len(self.free)} free "
                f"!= {self.n_blocks}"
            )
        if held != expected_held:
            raise RuntimeError(
                f"kv pool leak: {held} blocks held, expected {expected_held}"
            )

    def reset(self) -> None:
        """Forget every block (refcounts to zero, full free list). Data
        stays on device — unreferenced blocks are unreachable garbage.
        Bumps ``generation`` so outstanding handles no-op on release."""
        self.refs[:] = 0
        self.free = list(range(self.n_blocks - 1, -1, -1))
        self.version += 1
        self.generation += 1

    # -- data plane ----------------------------------------------------------

    def publish(self, caches: Params, row: int, ids, slots) -> None:
        """Copy live cache row ``row``'s prompt blocks into the pool:
        block ``slots[j]`` of the row (token span [slots[j]*block,
        (slots[j]+1)*block)) lands in pool block ``ids[j]``. One fused
        dispatch per (m, geometry); the pool tree is donated."""
        ids = np.asarray(ids, np.int32).reshape(-1)
        slots = np.asarray(slots, np.int32).reshape(-1)
        if ids.size == 0:
            return
        m, blk = int(ids.size), self.block
        seq = jax.tree.leaves(caches)[0].shape[-3]
        fn = RT.compiled(
            ("kv_publish", self.cfg, m, blk, seq, self.n_blocks), self._make_publish(m, blk)
        )
        self.data = fn(self.data, caches, jnp.asarray(int(row), jnp.int32),
                       jnp.asarray(ids), jnp.asarray(slots))
        self.version += 1
        self.counters["published"] += m

    def _make_publish(self, m: int, blk: int):
        def make():
            def f(data, caches, row, ids, slots):
                cols = (slots[:, None] * blk
                        + jnp.arange(blk, dtype=jnp.int32)[None]).reshape(-1)

                def leaf(pool, live):
                    src = jnp.take(live, row, axis=-4)       # drop batch axis
                    blocks = jnp.take(src, cols, axis=-3)
                    blocks = blocks.reshape(
                        src.shape[:-3] + (m, blk) + src.shape[-2:]
                    )
                    return pool.at[..., ids, :, :, :].set(
                        blocks.astype(pool.dtype)
                    )

                return jax.tree.map(leaf, data, caches)

            return jax.jit(f, donate_argnums=(0,))

        return make

    def copy_block(self, src: int) -> int:
        """Copy-on-write primitive: exclusive blocks are returned as-is;
        shared blocks are duplicated into a fresh allocation and the
        caller's reference moves to the copy."""
        if self.refs[src] < 1:
            raise RuntimeError(f"copy_block of free block {src}")
        if self.refs[src] == 1:
            return src
        dst = self.alloc(1)[0]
        fn = RT.compiled(("kv_copy", self.cfg, self.n_blocks, self.block),
                         self._make_copy)
        self.data = fn(self.data, jnp.asarray([src], jnp.int32),
                       jnp.asarray([dst], jnp.int32))
        self.deref([src])
        self.version += 1
        self.counters["cow_copies"] += 1
        return dst

    def _make_copy(self):
        def f(data, src, dst):
            return jax.tree.map(
                lambda x: x.at[..., dst, :, :, :].set(
                    jnp.take(x, src, axis=-4)
                ),
                data,
            )

        return jax.jit(f, donate_argnums=(0,))

    # -- checkpoint ----------------------------------------------------------

    def state_arrays(self) -> dict:
        """String-keyed dict tree of the data plane (the checkpoint loader
        only rebuilds dict nesting, so the periods list becomes
        ``{"0": ..., "1": ...}``)."""
        return {
            "periods": {
                str(i): p for i, p in enumerate(self.data["periods"])
            },
            "remainder": {
                str(j): r for j, r in enumerate(self.data["remainder"])
            },
        }

    def state_meta(self) -> dict:
        return {
            "n_blocks": self.n_blocks,
            "block": self.block,
            "refs": [int(r) for r in self.refs],
            "free": list(self.free),
            "version": self.version,
        }

    def load_state(self, arrays: dict, meta: dict) -> None:
        if (int(meta["n_blocks"]), int(meta["block"])) != (
            self.n_blocks, self.block
        ):
            raise ValueError(
                f"checkpoint kv pool ({meta['n_blocks']} x {meta['block']}) "
                f"!= this pool ({self.n_blocks} x {self.block}): restore "
                "requires an identically-sized block pool"
            )
        periods = [
            arrays["periods"][str(i)] for i in range(len(self.data["periods"]))
        ]
        remainder = [
            arrays["remainder"][str(j)]
            for j in range(len(self.data["remainder"]))
        ]
        data = {"periods": periods, "remainder": remainder}
        # Copies, never the caller's buffers: publish/copy donate the data
        # plane, and ``arrays`` may be another live pool's ``state_arrays()``.
        data = jax.tree.map(
            lambda ref, x: jnp.array(x, ref.dtype), self.data, data
        )
        # Same commitment rule as construction: restored data must land on
        # a concrete device so post-restore publishes reuse the jit cache.
        self.data = jax.device_put(
            data, self.device if self.device is not None else jax.devices()[0]
        )
        self.refs = np.asarray(meta["refs"], np.int32).copy()
        self.free = [int(i) for i in meta["free"]]
        self.version += 1
        self.generation += 1
