"""Single-owner KV state for the serve engine: the cache pytree, its
paged block tables, and the versioned-pinning discipline that makes
buffer donation sound.

Ownership model
---------------
Exactly one live version of the KV cache pytree exists at any time and
:class:`KVState` is its owner.  Every jitted step that rewrites the
cache (decode tick, verify, slot insert) donates the current version
and produces the next, and the rebind goes through
:meth:`KVState.commit` — nothing else ever holds the live tree.  That
single-owner rule is what makes **buffer donation** sound: with
``donate_argnums`` on the cache argument, XLA aliases the donated
input's device buffers into the output (verified per leaf on this
backend), so a decode tick updates the KV pool *in place* instead of
materialising a full copy — but the donated version is consumed (its
buffers are dead to Python), so a second holder of the old version
would be a use-after-free, not just a stale read.

Versioned pinning (the ``_retain`` workaround, made principled)
---------------------------------------------------------------
On this backend (jax 0.4.37 CPU) a device buffer whose last Python
reference drops can be recycled while a dispatched-but-pending
computation still reads it — observed as token corruption under serve
load; minimal standalone repro in ``examples/repro_buffer_lifetime.py``.
Non-donated arguments of pending steps (token rows, active masks, block
tables, prefill rows) therefore must stay referenced until a device
sync proves the dispatch chain has drained.  ``KVState`` owns that
discipline explicitly, replacing the engine's ad-hoc ``_retain`` list:

* :meth:`pin` — pin a *displaced* version (or a dispatch temporary) the
  moment it stops being engine state;
* :meth:`commit` — rebind the live cache to the next version.  The
  displaced version was donated: its lifetime is owned by the
  computation that consumed it, so it is never pinned — pinning it
  would hold a dead husk.  Donation and pinning must never overlap
  (asserted when ``debug_validate`` is on, and tested);
* :meth:`flush` — drop every pin at a proven sync point, or pay one
  bounded ``block_until_ready`` when the pin list hits its cap (an
  unbounded list pins whole cache versions: a leak with allocator
  stalls).

Paper mapping: a dispatch is a *block* (device work in flight, versions
pinned) and the sync that lets :meth:`flush` clear them is the matching
*unblock* — the same requirement the paper puts on monitored kernel
events (every block must pair with the unblock that releases it), here
applied to runtime-owned buffer lifetimes.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..models.layers import check_cache_invariant
from ..sharding import logical_sharding
from ..steps import TP_SERVE_RULES, init_paged_slot_cache, serve_cache_axes
from .pager import GARBAGE_PAGE, PagePool

# The engine-init guard and the per-block trace-time guard are the SAME
# contract (identical treedef + per-leaf shape/dtype, the XLA
# input/output aliasing precondition) — one implementation, two call
# sites, so the rule can never drift between layers.  Works on concrete
# arrays and on ``jax.eval_shape`` results alike.
alias_safe = check_cache_invariant


def _no_deleted_leaves(objs, where: str):
    for leaf in jax.tree.leaves(objs):
        deleted = getattr(leaf, "is_deleted", None)
        assert deleted is None or not deleted(), (
            f"{where}: a donated (deleted) buffer is pinned — donation "
            "and pinning must never overlap")


def cache_tree_shardings(cache, mesh):
    """Per-leaf ``NamedSharding`` tree for any cache with the serve leaf
    names — the paged slot pool or a prefill row cache (same
    leaf names and ranks throughout).  Resolution is strict: a mesh axis
    that does not divide the leaf dim is dropped (small head counts
    replicate, never pad — pjit argument shardings must divide evenly).
    Works on concrete arrays and ``jax.eval_shape`` results alike, so
    ``make_jit_steps`` can derive output shardings without a live pool."""
    def mk(path, leaf):
        name = (path[-1].key if hasattr(path[-1], "key")
                else str(path[-1]))
        return logical_sharding(
            leaf.shape, serve_cache_axes(name, len(leaf.shape)),
            mesh, TP_SERVE_RULES, strict=True)

    return jax.tree_util.tree_map_with_path(mk, cache)


class KVState:
    """Single owner of one slot pool's paged KV cache.

    Parameters mirror the engine's cache geometry.  The linear attention
    leaves are paged pools; ``KVState`` also owns the block table (host
    copy + device mirror, garbage page re-pointing) and the
    :class:`PagePool` free-list (``num_pages`` defaults to one full
    cache per slot + the garbage page).
    """

    def __init__(self, cfg, slots: int, cache_len: int, dtype, *,
                 page_size: int, num_pages: int | None = None,
                 pin_max: int = 64, mesh=None, tp: bool = False):
        self.cfg = cfg
        self.slots = slots
        self.cache_len = cache_len
        self.page_size = page_size
        # tensor-parallel serving: every cache leaf gets a per-leaf
        # NamedSharding (heads on the model axis, strict resolution — a
        # head count the axis cannot divide replicates, never pads: pjit
        # argument shardings must divide evenly) and every host->device
        # mirror is committed replicated, so the engine's host-side reads
        # (tokens, pos, tables) never shard
        self.mesh, self.tp = mesh, bool(tp)
        if self.tp:
            assert mesh is not None, "tp=True needs a mesh"
            self.rep = NamedSharding(mesh, P())
        else:
            self.rep = None
        assert cache_len % page_size == 0, (
            f"page_size {page_size} must divide cache_len {cache_len}")
        self.pages_per_slot = cache_len // page_size
        if num_pages is None:
            num_pages = slots * self.pages_per_slot + 1
        self.pager = PagePool(num_pages, page_size)
        self.cache = init_paged_slot_cache(cfg, slots, cache_len, dtype,
                                           page_size, num_pages)
        self._table = np.zeros((slots, self.pages_per_slot), np.int32)
        self.shardings = self.cache_shardings(self.cache)
        if self.tp:
            self.cache = jax.device_put(self.cache, self.shardings)
        # device mirrors are always a copy (jnp.array, or a committed
        # device_put of one under tp): asarray may alias the numpy
        # buffer, which async dispatch could read *after* a later
        # host-side mutation
        self.table_dev = self.to_dev(self._table)
        self._pins: list = []
        self.pin_max = pin_max
        self.version = 0
        self.pin_syncs = 0            # forced drains from a full pin list
        self.debug_validate = False   # tests: scan pins for dead buffers

    # ------------------------------------------------------------ sharding
    def cache_shardings(self, cache):
        """:func:`cache_tree_shardings` over ``cache``, or ``None`` when
        this state is not tensor-parallel."""
        if not self.tp:
            return None
        return cache_tree_shardings(cache, self.mesh)

    def to_dev(self, x):
        """Host value -> device mirror: always a fresh copy; committed
        replicated on the mesh under tp (so every shard's dispatch reads
        it locally) and a plain single-device copy otherwise."""
        if self.tp:
            return jax.device_put(jnp.array(x), self.rep)
        return jnp.array(x)

    # ------------------------------------------------------------ ownership
    def commit(self, new_cache) -> None:
        """Rebind the live cache to ``new_cache``.  The displaced version
        was donated to the jit call that produced ``new_cache`` (its
        buffers now belong to that execution), so it is **not** pinned."""
        self.cache = new_cache
        self.version += 1
        if self.debug_validate:
            self.assert_no_deleted_pins()

    def pin(self, *objs) -> None:
        """Pin device values a pending computation may still read: a
        displaced version of engine hot state (old token rows, old
        masks, old tables) or a dispatch temporary (prefill rows,
        scalar indices) whose Python references drop before the
        dispatch is known to have executed."""
        self._pins.append(objs)

    def flush(self, synced: bool) -> None:
        """Drop the pinned versions.  ``synced=True`` when the caller
        just forced the dispatch chain (every pinned buffer's reader has
        executed); otherwise flush only past the depth cap, paying one
        explicit drain first."""
        if synced:
            self._pins.clear()
        elif len(self._pins) > self.pin_max:
            jax.block_until_ready(self.cache["pos"])
            self.pin_syncs += 1
            self._pins.clear()

    @property
    def pins(self) -> int:
        return len(self._pins)

    def assert_no_deleted_pins(self) -> None:
        """The donation/pinning exclusivity invariant, checkable: no
        pinned leaf may be a donated (deleted) buffer."""
        _no_deleted_leaves(self._pins, "KVState pins")

    # ------------------------------------------------------------ block table
    def bind_slot_pages(self, slot: int, ids, *,
                        n_shared: int = 0) -> jnp.ndarray:
        """Point ``slot``'s block table at physical pages ``ids``
        (unreserved logical pages at the garbage page), refresh the
        device mirror (pinning the displaced one), and return the
        slot's table row as a device array for the insert step.

        ``n_shared`` (prefix-cache hit): the leading ``n_shared`` pages
        of ``ids`` are *shared* prefix pages — decode and the paged
        kernel read them through the real table, but the returned
        **insert row points them at the garbage page**.  The batched
        insert scatters every logical page of the prefilled row through
        its table row, and the insert jit *donates the pool*: writing a
        shared page in place would corrupt it for every other holder
        (and the prefilled row holds no valid content there anyway —
        prefill only computed the uncached tail).  This is the
        donation-safety rule made mechanical: a donated step never
        aliases a shared page it writes, because the write path never
        sees a shared page id."""
        assert 0 <= n_shared <= len(ids)
        self._table[slot, :] = GARBAGE_PAGE
        self._table[slot, :len(ids)] = ids
        self.sync_table()
        insert_row = self._table[slot].copy()
        insert_row[:n_shared] = GARBAGE_PAGE
        return self.to_dev(insert_row)

    def grow_slot_pages(self, slot: int, ids, *, base: int) -> None:
        """On-demand growth: bind physical pages ``ids`` at the slot's
        logical pages ``[base, base + len(ids))`` — the table extension a
        live slot needs when its ``pos`` crosses a page boundary
        mid-decode (policy: ``repro.serve.policy.OnDemandPolicy``).
        ``ids`` may span several logical pages at once: a speculative
        verify window (``spec_k`` drafts + 1 correction) can cross more
        than one page boundary in a single tick when ``spec_k >=
        page_size``, so the engine's fault pass grows the table to cover
        the whole window, not just the next position.  Host-side only;
        unlike :meth:`bind_slot_pages` (admission needs the device row
        immediately) the caller batches one :meth:`sync_table` per tick
        over every slot grown that tick."""
        assert 0 <= base and base + len(ids) <= self.pages_per_slot, (
            f"slot {slot}: grow [{base}, {base + len(ids)}) exceeds "
            f"{self.pages_per_slot} logical pages")
        assert (self._table[slot, base:base + len(ids)]
                == GARBAGE_PAGE).all(), "growing over live table entries"
        self._table[slot, base:base + len(ids)] = ids

    def release_slot_pages(self, slot: int) -> None:
        """Re-point a finished slot's table rows at the garbage page so
        the dead slot's frozen-pos cache writes land nowhere.  Host-side
        only — the caller refreshes the device mirror once per batch of
        releases (:meth:`sync_table`)."""
        self._table[slot, :] = GARBAGE_PAGE

    def sync_table(self) -> None:
        """Refresh the device block table from the host copy; the
        displaced mirror is an argument of pending decode dispatches,
        so it is pinned, not dropped."""
        self.pin(self.table_dev)
        self.table_dev = self.to_dev(self._table)

    # ------------------------------------------------------------ reporting
    def stats(self) -> dict:
        out = {
            "kv_version": self.version,
            "kv_pins": len(self._pins),
            "kv_pin_syncs": self.pin_syncs,
        }
        out.update(self.pager.stats())
        return out

    def __repr__(self):
        return (f"<KVState v{self.version} paged(ps={self.page_size}) "
                f"slots={self.slots} pins={len(self._pins)}>")
