"""Host-memory page swap: preempt a running sequence without losing it.

``SwapManager`` moves a victim sequence's KV pages out of the device
pool into host memory so the scheduler can hand its slot and pages to a
higher-priority request, and restores them bit-exactly when the victim
resumes. Three rules keep it cheap and prefix-cache-correct:

- **Shared pages are never copied.** A page mapped into another slot
  (refcount > 1) stays device-resident no matter what — copying it out
  would buy nothing. The manager *pins* the victim's shared prefix
  (``kv.incref``) so those pages survive until resume, then releases the
  pin once the resumed slot holds its own reference.
- **Radix-indexed pages park, they don't block.** A victim's private
  pages that the prefix cache still indexes are copied to host *and*
  parked (``free_slot``'s ``keep`` hook), so they remain evictable
  headroom for the preemptor; if they are still parked (or re-adopted by
  someone else) at resume time, the engine's radix re-match maps them
  straight back in and the host copy for those pages is simply dropped —
  the copy is a fallback, not the fast path.
- **The device→host copy is asynchronous.** ``swap_out`` gathers the
  victim's private pages into a standalone device array (a jit'd gather
  — by XLA's functional semantics the preemptor reusing the freed pages
  cannot corrupt it), starts a non-blocking transfer
  (``copy_to_host_async``), and returns immediately; the engine calls
  ``finalize`` after the *next* decode step, overlapping the DMA with
  real work, which drops the device-side staging copy.

Page-count shapes are padded to powers of two (padding rows gather from
/ scatter to the trash page 0, whose contents every read masks), so the
gather/scatter programs stay O(log) like every other serving jit.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.guards import hot_path
from repro.distributed import sharding as sharding_lib
from repro.obs import MetricsRegistry
from repro.serving.kv_cache import PagedKVCache

__all__ = ["SwapManager", "SwapRecord", "SwapStats"]


@jax.jit
def _gather_pages(buffers, idx: jax.Array):
    """Pull pages ``idx`` out of every layer pool into standalone
    (layers, n, kv_heads, page, head_dim) staging arrays."""
    return jax.tree.map(lambda b: b[:, idx], buffers)


def _scatter_pages_impl(buffers, idx: jax.Array, data, *, shardings):
    """Write staged page data back into pool pages ``idx`` (duplicate
    trash-page padding entries all target page 0, whose contents are
    masked by every read)."""
    out = jax.tree.map(
        lambda b, d: b.at[:, idx].set(d), buffers, data
    )
    return sharding_lib.constrain_pools(out, shardings)


def _pad_pow2(pages: list[int]) -> np.ndarray:
    n = 1 << (len(pages) - 1).bit_length() if len(pages) > 1 else 1
    idx = np.zeros((n,), np.int32)  # padding rows hit the trash page
    idx[: len(pages)] = pages
    return idx


@dataclasses.dataclass
class SwapRecord:
    """Everything needed to restore one swapped-out sequence."""

    slot_was: int
    # the victim's shared logical-prefix pages, kept live by one pin
    # each until resume (released by ``swap_in``/``discard``)
    pin_pages: list[int]
    # host-copied logical pages [len(pin_pages), n_logical)
    n_host: int
    # staging tree: device arrays until ``finalize``, numpy after
    host: list | None
    # True while ``host`` still holds device arrays
    pending: bool = False

    @property
    def n_logical(self) -> int:
        return len(self.pin_pages) + self.n_host


class SwapStats:
    """View over the engine's metrics registry (see `repro.obs`); the
    attribute surface (``swap_outs`` etc.) is unchanged from the ad-hoc
    int era so tests and callers keep reading plain numbers."""

    def __init__(self, registry: MetricsRegistry | None = None):
        reg = registry if registry is not None else MetricsRegistry()
        self.registry = reg
        self._swap_outs = reg.counter(
            "repro_swap_outs_total", "sequences swapped out to host"
        )
        self._swap_ins = reg.counter(
            "repro_swap_ins_total", "sequences swapped back in"
        )
        self._out_pages = reg.counter(
            "repro_swap_out_pages_total", "pages copied device->host"
        )
        self._in_pages = reg.counter(
            "repro_swap_in_pages_total", "pages copied host->device"
        )
        self._out_bytes = reg.counter(
            "repro_swap_out_bytes_total", "bytes copied device->host"
        )
        self._in_bytes = reg.counter(
            "repro_swap_in_bytes_total", "bytes copied host->device"
        )
        # shared pages spared the copy
        self._pinned_pages = reg.counter(
            "repro_swap_pinned_pages_total",
            "shared pages pinned in place of a copy",
        )

    swap_outs = property(lambda self: self._swap_outs.value)
    swap_ins = property(lambda self: self._swap_ins.value)
    out_pages = property(lambda self: self._out_pages.value)
    in_pages = property(lambda self: self._in_pages.value)
    out_bytes = property(lambda self: self._out_bytes.value)
    in_bytes = property(lambda self: self._in_bytes.value)
    pinned_pages = property(lambda self: self._pinned_pages.value)

    def record_out(self, pages: int, bytes_: int, pinned: int) -> None:
        self._swap_outs.inc()
        self._out_pages.inc(pages)
        self._out_bytes.inc(bytes_)
        self._pinned_pages.inc(pinned)

    def record_in(self, pages: int, bytes_: int) -> None:
        self._in_pages.inc(pages)
        self._in_bytes.inc(bytes_)

    def record_swap_in(self) -> None:
        self._swap_ins.inc()

    def snapshot(self) -> dict:
        return {
            "swap_outs": self.swap_outs,
            "swap_ins": self.swap_ins,
            "out_pages": self.out_pages,
            "in_pages": self.in_pages,
            "out_bytes": self.out_bytes,
            "in_bytes": self.in_bytes,
            "pinned_pages": self.pinned_pages,
        }


class SwapManager:
    def __init__(
        self,
        kv: PagedKVCache,
        *,
        page_in_tree: Callable[[int], bool] | None = None,
        metrics: MetricsRegistry | None = None,
    ):
        """``page_in_tree``: the prefix cache's membership probe (None
        when the cache is off) — used both as ``free_slot``'s keep hook
        (private indexed pages park instead of freeing) and to classify
        which pages the radix re-match can restore without a copy.
        ``metrics``: the engine's shared registry (the stats view
        creates a private one when absent)."""
        self.kv = kv
        self.page_in_tree = page_in_tree
        self.stats = SwapStats(metrics)
        # Restore scatter, jit'd per manager so the sharded-pool layout
        # pin (constrain_pools, jaxlint JL005) closes over this pool's
        # shardings; single-device pools close over None (no-op).
        self._scatter_pages = jax.jit(
            functools.partial(_scatter_pages_impl, shardings=kv.shardings),
            donate_argnums=(0,),
        )
        # bytes one page occupies across every layer pool
        self.page_bytes = sum(
            int(np.prod(b.shape[0:1] + b.shape[2:])) * b.dtype.itemsize
            for pool in kv.buffers
            for b in pool.values()
        )

    # ---- out ---------------------------------------------------------
    def swap_out(self, slot: int, *, max_pin: int | None = None) -> SwapRecord:
        """Evacuate ``slot``: pin its shared logical-prefix pages (no
        copy), stage every other owned page for an async device→host
        copy, and free the slot. ``max_pin`` caps how many leading pages
        may be pinned instead of copied (the engine passes the radix
        match cap, ``(plen - 1) // page``, so resume's re-match is
        always able to cover the pinned prefix). Returns immediately —
        call ``finalize`` after the next decode step."""
        kv = self.kv
        owned = kv.owned_pages(slot)
        if not owned:
            raise ValueError(f"slot {slot} owns no pages (nothing to swap)")
        n_pin = 0
        cap = len(owned) if max_pin is None else min(max_pin, len(owned))
        while n_pin < cap and kv.refcount(owned[n_pin]) > 1:
            n_pin += 1
        pin_pages, host_pages = owned[:n_pin], owned[n_pin:]
        host = None
        if host_pages:
            idx = _pad_pow2(host_pages)
            host = _gather_pages(kv.buffers, jnp.asarray(idx))
            for leaf in jax.tree.leaves(host):
                leaf.copy_to_host_async()
        for p in pin_pages:
            kv.incref(p)  # survives until swap_in/discard releases it
        kv.free_slot(slot, keep=self.page_in_tree)
        self.stats.record_out(
            len(host_pages), len(host_pages) * self.page_bytes, n_pin
        )
        return SwapRecord(
            slot_was=slot,
            pin_pages=pin_pages,
            n_host=len(host_pages),
            host=host,
            pending=host is not None,
        )

    @hot_path
    def finalize(self, record: SwapRecord) -> None:
        """Materialize the staged copy on the host and drop the
        device-side staging arrays (freeing their pool-sized device
        footprint). The async transfer has been overlapping the decode
        step(s) since ``swap_out``; this is at worst a short wait."""
        if not record.pending:
            return
        # One batched fetch of the whole staging tree; the DMA has been
        # in flight since swap_out, so this lands, not blocks.
        record.host = jax.device_get(record.host)  # jaxlint: disable=JL001 -- the sanctioned explicit sync that lands an async swap-out transfer
        record.pending = False

    # ---- in ----------------------------------------------------------
    def swap_in(
        self, record: SwapRecord, slot: int, *, n_resident: int
    ) -> None:
        """Restore a swapped sequence into ``slot``. The engine has
        already mapped logical pages [0, n_resident) — the pinned prefix
        plus whatever the radix re-match recovered beyond it — and
        allocated fresh pages for [n_resident, n_logical); this scatters
        the host copies into those fresh pages and releases the record's
        pins (each pinned page is now held by the slot's own
        reference)."""
        kv = self.kv
        n_pin = len(record.pin_pages)
        if n_resident < n_pin:
            raise ValueError(
                f"resume re-match covered {n_resident} pages but "
                f"{n_pin} were pinned — pinned pages stay matchable"
            )
        if kv.pages_owned(slot) < record.n_logical:
            raise ValueError(
                f"slot {slot} owns {kv.pages_owned(slot)} pages; "
                f"restore needs {record.n_logical}"
            )
        if record.n_host:
            self.finalize(record)  # no-op if already materialized
            idx = np.zeros((_pad_pow2([0] * record.n_host).size,), np.int32)
            # host row j holds logical page n_pin + j; rows the re-match
            # already covered scatter to the trash page (dropped)
            restored = 0
            for j in range(record.n_host):
                li = n_pin + j
                if li < n_resident:
                    continue
                idx[j] = int(kv.page_table[slot, li])
                restored += 1
            kv.buffers = self._scatter_pages(
                kv.buffers,
                jnp.asarray(idx),
                jax.tree.map(jnp.asarray, record.host),
            )
            self.stats.record_in(restored, restored * self.page_bytes)
        for p in record.pin_pages:
            kv.unpin(p)
        record.host = None
        self.stats.record_swap_in()

    def discard(self, record: SwapRecord) -> None:
        """Abandon a swapped sequence (it was cancelled or timed out):
        release the pins and drop the host copy."""
        for p in record.pin_pages:
            self.kv.unpin(p)
        record.pin_pages = []
        record.host = None
        record.pending = False
