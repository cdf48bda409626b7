"""Buffer-lease ownership contract for the zero-copy batch path (ISSUE 6).

Every hop of the read path used to defend itself with a private memcpy: the
default wires copied read-only reconstructions writable, ``MemCache`` deep-
copied on both hit and admit, and the loader copied every slab view out before
buffering ("Zerrow: True Zero-Copy Arrow Pipelines in Bauplan", PAPERS.md,
names the cure: one buffer-ownership contract so a row group is materialized
once and only sliced/viewed afterward). This module is that contract:

- :class:`Lease` — a refcounted handle over read-only buffers owned by someone
  else (a slab ring, the memcache store, a pinned staging pool). ``retain()``
  adds a holder, ``release()`` drops one; the owner's reclaim callback fires
  exactly once, when the LAST holder releases. ``revoke()`` lets the owner
  invalidate outstanding views (executor rebuild on ``Reader.reset()``):
  lease-aware accessors then raise :class:`~petastorm_tpu.errors.LeaseRevoked`
  instead of returning garbage.
- :class:`LeasedBatch` — a columnar batch dict riding one or more leases.
  Column access checks revocation; ``writable()`` is the copy-on-write
  escalation (copy ONE column, count the bytes, only when a consumer actually
  writes).
- The **copy census** — ``count_copy(site, nbytes)`` at every remaining copy
  site, exported as ``ptpu_copy_bytes_total{site=...}`` counters on the PR-3
  default registry. ``petastorm-tpu-bench copies`` reads the census deltas to
  report bytes-copied-per-delivered-batch per path.

Discipline rules (enforced at runtime here, statically by graftlint GL-L001):
release exactly once per retain; never touch buffers after your release; a
dropped lease self-releases at GC (``__del__``) so an abandoned batch cannot
wedge a ring — but the drop is counted as ``ptpu_lease_leaked_total`` because
it makes slab return nondeterministic.
"""
from __future__ import annotations

import threading

import numpy as np

from petastorm_tpu.errors import LeaseError, LeaseRevoked

#: reserved key under which a batch's lease rides inside a tagged columnar
#: payload dict crossing a wire — the Reader pops it before exposing the batch
#: (generalizes the PR-2 ``__shm_lease__`` key to any lease-backed transport)
LEASE_KEY = "__lease__"


class _LeaseMetrics:
    """Process-wide ``ptpu_lease_*`` counters (built on first lease; the
    registry import stays off the module import path)."""

    __slots__ = ("acquired", "released", "retained", "cow", "revoked", "leaked",
                 "active")

    def __init__(self):
        from petastorm_tpu.obs.metrics import default_registry

        reg = default_registry()
        self.acquired = reg.counter(
            "ptpu_lease_acquired_total", help="leases created over borrowed buffers")
        self.released = reg.counter(
            "ptpu_lease_released_total",
            help="leases fully released (owner reclaim callback fired)")
        self.retained = reg.counter(
            "ptpu_lease_retained_total", help="additional holders added via retain()")
        self.cow = reg.counter(
            "ptpu_lease_cow_total",
            help="copy-on-write escalations (a consumer actually wrote)")
        self.revoked = reg.counter(
            "ptpu_lease_revoked_total",
            help="leases invalidated by their buffer owner (reset/teardown)")
        self.leaked = reg.counter(
            "ptpu_lease_leaked_total",
            help="leases reclaimed by GC instead of an explicit release")
        self.active = reg.gauge(
            "ptpu_lease_active", help="leases currently alive (refcount > 0)")


_metrics_lock = threading.Lock()
_metrics = None


def _lease_metrics():
    global _metrics
    if _metrics is None:
        with _metrics_lock:
            if _metrics is None:
                _metrics = _LeaseMetrics()
    return _metrics


class Lease:
    """One refcounted claim over read-only buffers owned by someone else.

    The constructor is the acquire (refcount 1). ``release_cb`` is the owner's
    reclaim hook — return a slab to its ring, unpin a staging slot — and fires
    exactly once, when the count reaches zero. Thread-safe: batches cross the
    loader's producer/transfer/consumer threads and each may hold a retain.
    """

    __slots__ = ("_release_cb", "_refs", "_lock", "_revoked", "kind",
                 "__weakref__")

    def __init__(self, release_cb=None, kind="lease"):
        self._release_cb = release_cb
        self._refs = 1
        self._lock = threading.Lock()
        self._revoked = False
        self.kind = kind
        m = _lease_metrics()
        m.acquired.inc()
        m.active.inc()

    # -- refcount protocol --------------------------------------------------------------

    def retain(self):
        """Add a holder; returns ``self`` so call sites read
        ``batch_leases.append(lease.retain())``."""
        with self._lock:
            if self._refs <= 0:
                raise LeaseError(
                    "retain() on a fully-released %s lease: its buffers are "
                    "already back with their owner" % self.kind)
            self._refs += 1
        _lease_metrics().retained.inc()
        return self

    def release(self):
        """Drop one holder; the owner's reclaim callback runs at zero. Releasing
        past zero raises :class:`~petastorm_tpu.errors.LeaseError` (never
        silently double-frees a buffer into two consumers)."""
        with self._lock:
            if self._refs <= 0:
                raise LeaseError(
                    "release() on an already-released %s lease (double "
                    "release)" % self.kind)
            self._refs -= 1
            final = self._refs == 0
        if final:
            self._reclaim()

    def _reclaim(self):
        cb, self._release_cb = self._release_cb, None
        m = _lease_metrics()
        m.released.inc()
        m.active.dec()
        if cb is not None:
            cb()

    # -- revocation ---------------------------------------------------------------------

    def revoke(self):
        """Owner-side invalidation: outstanding views must no longer be read
        (the backing memory is being recycled). Holders keep their refcounts —
        their ``release()`` calls stay balanced — but :meth:`check` and every
        :class:`LeasedBatch` accessor raise from now on."""
        with self._lock:
            if self._revoked:
                return
            self._revoked = True
        _lease_metrics().revoked.inc()

    @property
    def revoked(self):
        return self._revoked

    @property
    def alive(self):
        return self._refs > 0

    def check(self):
        """Raise :class:`~petastorm_tpu.errors.LeaseRevoked` when the buffers
        behind this lease were invalidated by their owner."""
        if self._revoked:
            raise LeaseRevoked(
                "%s lease was revoked by its buffer owner (e.g. Reader.reset() "
                "rebuilt the executor backing this batch); the views are no "
                "longer valid" % self.kind)

    # -- GC safety net ------------------------------------------------------------------

    def __del__(self):
        try:
            with self._lock:
                refs, self._refs = self._refs, 0
            if refs > 0:
                # abandoned holder(s): reclaim so the owner's pool cannot wedge,
                # but count it — GC-timed buffer return is a caller bug
                _lease_metrics().leaked.inc()
                self._reclaim()
        except Exception:  # noqa: BLE001 — interpreter teardown
            pass  # graftlint: disable=GL-O002 (GC/exit path: metrics may be torn down)

    def __repr__(self):
        return "<Lease kind=%s refs=%d%s>" % (
            self.kind, self._refs, " REVOKED" if self._revoked else "")


class LeasedBatch(dict):
    """A columnar batch (``{name: ndarray}``) riding the lease(s) that own its
    buffers. Behaves as a plain dict for the hot paths; key access additionally
    checks revocation, and :meth:`writable` is the CoW escalation.

    ``leases`` holds the retained handles this batch owns; :meth:`release`
    drops them all exactly once (idempotent at the batch level so consumer
    teardown paths stay simple — the per-lease discipline is still enforced).
    """

    __slots__ = ("leases",)

    def __init__(self, columns=(), leases=()):
        super().__init__(columns)
        self.leases = tuple(leases)

    def _check(self):
        for lease in self.leases:
            lease.check()

    def __getitem__(self, key):
        self._check()
        return super().__getitem__(key)

    # every accessor that can hand out buffer views checks revocation too —
    # a consumer iterating ``batch.items()`` after Reader.reset() must get
    # LeaseRevoked, not views into a recycled slab
    def get(self, key, default=None):
        self._check()
        return super().get(key, default)

    def items(self):
        self._check()
        return super().items()

    def values(self):
        self._check()
        return super().values()

    def writable(self, name):
        """Copy-on-write escalation for ONE column: replaces the read-only view
        with an owned writable copy (counted in the copy census) and returns
        it. The lease keeps protecting the remaining view columns."""
        arr = self[name]
        if isinstance(arr, np.ndarray) and not arr.flags.writeable:
            _lease_metrics().cow.inc()
            arr = arr.copy()
            count_copy("lease_cow", arr.nbytes)
            super().__setitem__(name, arr)
        return arr

    def release(self):
        """Release every lease this batch retained (exactly once per batch)."""
        leases, self.leases = self.leases, ()
        for lease in leases:
            lease.release()


def attach_leases(batch, leases):
    """Wrap ``batch`` (a plain columnar dict) as a :class:`LeasedBatch` holding
    ``leases``; a no-op returning ``batch`` unchanged when there are none."""
    if not leases:
        return batch
    if isinstance(batch, LeasedBatch):
        batch.leases = tuple(batch.leases) + tuple(leases)
        return batch
    return LeasedBatch(batch, leases)


def take_leases(batch):
    """Detach and return a batch's leases (``()`` for plain dicts): ownership
    moves to the caller, which must release them when the batch completes."""
    if isinstance(batch, LeasedBatch):
        leases, batch.leases = batch.leases, ()
        return leases
    return ()


def readonly_view(value):
    """Recursively rebuild ``value`` with every ndarray replaced by a READ-ONLY
    zero-copy view (fresh containers, shared buffers): the shape served by
    lease-backed stores. Object-dtype arrays get fresh outer arrays whose
    ndarray ELEMENTS are read-only views too (the outer pointers are copied —
    bytes negligible — so element reassignment stays consumer-local)."""
    if isinstance(value, np.ndarray):
        if value.dtype == object:
            out = np.empty(value.shape, dtype=object)
            out_flat, in_flat = out.reshape(-1), value.reshape(-1)
            for i in range(in_flat.size):
                out_flat[i] = readonly_view(in_flat[i])
            return out
        view = value.view()
        view.flags.writeable = False
        return view
    if isinstance(value, dict):
        return {k: readonly_view(v) for k, v in value.items()}
    if isinstance(value, list):
        return [readonly_view(v) for v in value]
    if isinstance(value, tuple):
        return tuple(readonly_view(v) for v in value)
    return value


def detach_slab_views(columns):
    """Copy every zero-copy slab view out of a view-mode reader delivery before it
    enters a buffering stage: top-level read-only ndarrays, read-only ELEMENTS of
    object (ragged) columns, and staged payload objects exposing ``detach()`` —
    all go stale when the Reader releases the batch's lease at its next fetch.

    This is the fallback for buffering the lease cannot ride (shuffling buffers,
    per-row readers, host-only columns handed to the consumer); the plain
    batched path retains the delivery's lease instead of copying. Bytes copied
    here are charged to the ``loader_detach`` census site."""
    out = {}
    copied = 0
    for name, v in columns.items():
        if isinstance(v, np.ndarray):
            if v.dtype.hasobject:
                fresh = np.empty(v.shape, dtype=object)
                for idx, e in np.ndenumerate(v):
                    if isinstance(e, np.ndarray) and not e.flags.writeable:
                        copied += e.nbytes
                        e = e.copy()
                    elif hasattr(e, "detach"):
                        e = e.detach()
                    fresh[idx] = e
                v = fresh
            elif not v.flags.writeable:
                copied += v.nbytes
                v = v.copy()
        out[name] = v
    count_copy("loader_detach", copied)
    return out


# --------------------------------------------------------------------------------------
# Copy census: ptpu_copy_bytes_total{site=...}
# --------------------------------------------------------------------------------------

#: the known copy sites (docs/performance.md "Copy census"): new sites register
#: lazily, this is documentation + a typo tripwire for the bench assertions
KNOWN_SITES = (
    "wire_writable",    # default-wire writable-contract copy (serializers)
    "wire_owned",       # shm pickle payload backed by owned buffers (serializers)
    "memcache_hit",     # legacy writable-hit deep copy (memcache writable mode)
    "memcache_admit",   # legacy miss-path defensive copy (memcache writable mode)
    "memcache_cow",     # explicit writable escalation on a leased entry
    "lease_cow",        # LeasedBatch.writable() escalation
    "loader_detach",    # loader copy-out of view columns (shuffle / host-only)
    "loader_concat",    # batcher cross-chunk concatenation
    "loader_pad",       # last_batch='pad' index gather
    "h2d_stage",        # pinned staging copy before device_put
    "h2d_owned_copy",   # owned copy before an aliasing (CPU) device_put
    "arena_admit",      # the ONE copy into the shared cache arena (io/arena.py)
)

_census_lock = threading.Lock()
_census = {}  # site -> Counter on the default registry


def _site_counter(site):
    counter = _census.get(site)
    if counter is None:
        from petastorm_tpu.obs.metrics import default_registry

        with _census_lock:
            counter = _census.get(site)
            if counter is None:
                counter = default_registry().counter(
                    "ptpu_copy_bytes_total",
                    help="payload bytes memcpy'd on the batch path, by site",
                    site=site)
                _census[site] = counter
    return counter


def count_copy(site, nbytes):
    """Charge ``nbytes`` to ``site`` in the copy census (cheap: one counter
    inc; callers batch per payload, not per array element)."""
    if nbytes:
        _site_counter(site).inc(int(nbytes))


def copy_census():
    """Snapshot ``{site: total_bytes}`` — what ``petastorm-tpu-bench copies``
    diffs around a measured window."""
    with _census_lock:
        return {site: counter.value for site, counter in _census.items()}


def lease_stats():
    """Snapshot of the ``ptpu_lease_*`` counters as a flat dict (collector
    shape, for private-registry loaders and the bench summary)."""
    m = _lease_metrics()
    return {
        "acquired": m.acquired.value,
        "released": m.released.value,
        "retained": m.retained.value,
        "cow": m.cow.value,
        "revoked": m.revoked.value,
        "leaked": m.leaked.value,
        "active": m.active.value,
    }
