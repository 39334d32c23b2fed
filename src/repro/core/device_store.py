"""Device-resident fleet batch with incremental selection statistics.

The selection loop (§III-A) only ever consumes per-model accuracy and the
pairwise similarity Gram matrix, yet the restack path re-uploads and
re-derives both from the raw `(N, M, V, C)` prediction tensors on every
debounced re-selection. `DeviceStoreBatch` keeps the fleet's stacked
preds/labels/mask tensors ON DEVICE together with persistent per-client
statistics — `acc (N, M)` and `S (N, M, M)` — and updates them
incrementally (DESIGN.md §7):

- host stores log dirty `(client, slot)` events on add/evict (the
  `PredictionStore.dirty_seq` slot→event-id map, drained via per-batch
  cursors so several device mirrors can track one fleet independently);
- `flush()` drains those events into ONE jitted donated-buffer scatter:
  only the changed `(V, C)` rows cross the host→device boundary, once
  each (`.at[ci, si].set`, the batched `dynamic_update_slice`), and only the
  affected `acc[c, slot]` entries and `S[c, slot, :]` / `S[c, :, slot]`
  row/column pairs are recomputed — `O(dirty · M · V · C)` instead of the
  full `O(N · M² · V · C)` rebuild;
- eviction coherence: `StreamingPredictionStore._evict_one` zeroes the
  host row and enqueues the slot, so the next flush zeroes the device row,
  drops the mask, and overwrites the cached stats for that slot.

Every pairwise similarity is computed by the SAME row contraction (a
normalized-row matvec over the flattened `V·C` axis against the final
occupant rows) regardless of the order in which slots became dirty, so
incremental state is bit-identical to a from-scratch flush of the same
stores — the parity the engine's sync-equals-async determinism tests
rely on.

Donation: the flush jit donates the five mutable buffers (preds, pnorm,
masks, acc, S), so steady-state updates run in place on device backends;
after every flush the batch REPLACES its references (use-after-donate
safety — the old handles are dead on backends that honor donation).

Dirty slots are grouped per client; the group count and the per-client
slot width are each padded to the next power of two (repeating groups /
slots — scatter and recompute are idempotent), so an async run compiles
O(log N · log M) flush variants, mirroring the engine's client-batch
padding. The padding never crosses the host→device boundary: each
distinct dirty row is copied once into a host block the batch keeps
across flushes (U rows, U the next power of two of the distinct rows),
and `_expand` pads it on the device through an int32 index of the K·R
padded positions. When every client is dirty the per-group block gather
is elided and the matmul reads the resident normalized tensor directly.

Observability (DESIGN.md §11): `n_bytes_uploaded` counts the host→device
bytes of every flush (the compact row block and its index arrays), and
`n_bytes_useful` the part of them that carries real validation samples
of distinct dirty slots; `n_rows_uploaded` counts the rows of the
uploaded blocks (U) and `n_rows_expanded` the padded rows the device
expands them to (K·R). With an enabled `metrics` registry the flush
opens host spans (`store.flush` and its drain/assemble/upload/launch
children) and mirrors the four counts (`store.upload_bytes`,
`store.useful_bytes`, `store.upload_rows`, `store.expanded_rows`); the
jitted programs carry device scopes (`flush`, `flush/scatter`,
`flush/gram`, `gather`) in their op names.
"""
from __future__ import annotations

from functools import partial
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.objectives import F32
from repro.obs.metrics import NULL_METRICS


def _pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def _zero_row_acc(label_row: np.ndarray) -> np.float32:
    """member_accuracy of an all-zero prediction row: argmax ties resolve
    to class 0, so empty slots score the label-0 fraction. Seeding the
    cached acc with this keeps never-materialized slots bit-identical to
    a from-scratch full-stats rebuild (they are masked out of selection
    either way)."""
    valid = label_row >= 0
    nv = max(int(valid.sum()), 1)
    return np.float32(int(((label_row == 0) & valid).sum())) / np.float32(nv)


@jax.jit
def _expand(rows, row_mask, src):
    """The padded dirty-row block from the compact upload: rows (U, V, C)
    and row_mask (U,) hold each distinct dirty row once, and src (K·R,)
    maps every padded (group, slot) position to its row. A program of
    its own, so that `_flush` compiles and rounds exactly as it does on
    a padded block uploaded whole: inside `_flush` the TPU lays the
    gathered block out otherwise, and its row norms round differently."""
    with jax.named_scope("flush"), jax.named_scope("scatter"):
        return rows[src], row_mask[src]


@partial(jax.jit, static_argnames=("all_clients",),
         donate_argnums=(0, 1, 2, 3, 4))
def _flush(preds, pnorm, masks, acc, S, labels, nv, rows, row_mask,
           cu, slots, all_clients: bool = False):
    """Scatter the dirty rows and recompute only their statistics.

    preds (N, M, V, C) / pnorm (its cached normalized mirror) /
    masks (N, M) / acc (N, M) / S (N, M, M) are the DONATED device
    buffers; labels (N, V) and nv (N,) are read-only. The drained dirty
    events arrive GROUPED BY CLIENT: cu (K,) dirty-client ids, slots
    (K, R) their dirty slot ids (padded by repeating — idempotent),
    rows (K·R, V, C) the raw prediction rows, row_mask (K·R,) their
    presence bits. `all_clients=True` asserts cu == arange(N), eliding
    the (K, M, V, C) client-block gather entirely.

    Keeping `pnorm` resident is what makes the incremental Gram update
    cheap: only the K·R incoming rows are normalized, and each dirty
    client's S rows/columns are ONE (R, V·C) x (V·C, M) matmul against
    its normalized block, read once — `O(dirty · M · V · C)` total, vs
    the full rebuild's normalize-everything + `O(N · M² · V · C)` Gram.
    """
    K, R = slots.shape
    with jax.named_scope("flush"), jax.named_scope("scatter"):
        ci = jnp.repeat(cu, R)                   # (K·R,) flat client ids
        si = slots.reshape(-1)                   # (K·R,) flat slot ids
        lab = labels[ci]                         # (K·R, V)
        valid = (lab >= 0)
        rn = rows / (jnp.linalg.norm(rows, axis=-1, keepdims=True) + 1e-12)
        rn = rn * valid[:, :, None].astype(jnp.float32)
        preds = preds.at[ci, si].set(rows)
        pnorm = pnorm.at[ci, si].set(rn)
        masks = masks.at[ci, si].set(row_mask)
        hit = (jnp.argmax(rows, axis=-1) == lab) & valid
        acc = acc.at[ci, si].set(
            jnp.sum(hit.astype(jnp.float32), axis=-1) / nv[ci])
    with jax.named_scope("flush"), jax.named_scope("gram"):
        block = pnorm if all_clients else pnorm[cu]  # (K, M, V, C)
        # contract over the FLATTENED (V·C) axis: a free reshape of the
        # contiguous trailing dims — the two-axis (v, c) contraction
        # makes XLA:CPU transpose-copy the whole resident tensor first
        rg = rn.reshape(K, R, -1)
        srows = (jnp.einsum("krx,kmx->krm", rg,
                            block.reshape(block.shape[0], block.shape[1],
                                          -1),
                            precision=F32)
                 / nv[cu][:, None, None])
        S = S.at[cu[:, None], slots].set(srows)      # dirty rows ...
        S = S.at[cu[:, None], :, slots].set(srows)   # ... + symmetric columns
    return preds, pnorm, masks, acc, S


@jax.jit
def _gather(preds, labels, masks, acc, S, idx):
    """Power-of-two client-batch gather, entirely on device."""
    take = lambda a: jnp.take(a, idx, axis=0)  # noqa: E731
    with jax.named_scope("gather"):
        return take(preds), take(labels), take(masks), take(acc), take(S)


class DeviceStoreBatch:
    """Device mirror of a fleet of `PredictionStore`s + cached (acc, S)."""

    def __init__(self, stores, v_max: Optional[int] = None):
        stores = list(stores)
        assert stores, "DeviceStoreBatch needs at least one store"
        cap = stores[0].capacity
        C = stores[0].n_classes
        self.v_max = max(s.v_pad for s in stores) if v_max is None else v_max
        self.capacity, self.n_classes = cap, C
        self.stores: List = []
        self._dirty: List[set] = []        # per-client pending slot events
        self._cursor: List[int] = []       # per-client dirty-log position
        self.n_flushes = 0
        self.n_rows_scattered = 0          # perf counters (bench/DESIGN §7)
        self.n_rows_uploaded = 0           # rows of the uploaded blocks
        self.n_rows_expanded = 0           # ... after the K/R padding
        self.n_bytes_uploaded = 0          # host->device bytes of flushes
        self.n_bytes_useful = 0            # ... that are real dirty rows
        self.metrics = NULL_METRICS        # the engine's registry (§11)
        self._stage = {}                   # U -> host upload block
        labels = np.full((len(stores), self.v_max), -1, np.int32)
        self.preds = jnp.zeros((len(stores), cap, self.v_max, C), jnp.float32)
        self.pnorm = jnp.zeros_like(self.preds)  # cached normalized mirror
        self.masks = jnp.zeros((len(stores), cap), jnp.float32)
        self.S = jnp.zeros((len(stores), cap, cap), jnp.float32)
        for i, s in enumerate(stores):
            self._attach(s, labels[i])
        self.labels = jnp.asarray(labels)
        # valid samples per client on the host (the useful-bytes count),
        # and in fp32 on device, the shared denominator of acc and S
        self._n_valid = (labels >= 0).sum(1)
        self.nv = jnp.asarray(np.maximum(self._n_valid, 1)
                              .astype(np.float32))
        acc0 = np.stack([np.full((cap,), _zero_row_acc(labels[i]), np.float32)
                         for i in range(len(stores))])
        self.acc = jnp.asarray(acc0)

    # ---- membership ---------------------------------------------------
    def _attach(self, store, label_row: np.ndarray):
        assert store.capacity == self.capacity, "capacity mismatch"
        assert store.n_classes == self.n_classes, "n_classes mismatch"
        if store.v_pad > self.v_max:
            raise ValueError(
                f"store v_pad={store.v_pad} exceeds the device batch pad "
                f"v_max={self.v_max}; provision the batch (engine v_max=...) "
                "for the widest validation set that can ever join")
        label_row[:store.v_pad] = store.labels
        self.stores.append(store)
        # everything already materialized (plus anything the store logged
        # before attach) is pending until the first flush; the cursor is
        # OURS — other device mirrors of the same store drain the log
        # with their own cursors, nothing is destructively cleared
        self._dirty.append(set(np.flatnonzero(store.mask))
                           | set(store.dirty_seq))
        self._cursor.append(store._dirty_clock)

    def append_store(self, store):
        """Grow the fleet by one client (churn join). The device buffers
        are reallocated with one extra row; the newcomer's slots flush on
        the next `flush()`."""
        labels = np.asarray(self.labels)
        row = np.full((1, self.v_max), -1, np.int32)
        self._attach(store, row[0])
        self.labels = jnp.asarray(np.concatenate([labels, row]))
        self._n_valid = np.concatenate([self._n_valid, (row >= 0).sum(1)])
        self.nv = jnp.concatenate([self.nv, jnp.asarray(
            np.maximum((row >= 0).sum(1), 1).astype(np.float32))])
        grow = lambda a: jnp.concatenate(  # noqa: E731
            [a, jnp.zeros((1,) + a.shape[1:], a.dtype)])
        self.preds, self.pnorm = grow(self.preds), grow(self.pnorm)
        self.masks, self.S = grow(self.masks), grow(self.S)
        acc_row = np.full((1, self.capacity), _zero_row_acc(row[0]),
                          np.float32)
        self.acc = jnp.concatenate([self.acc, jnp.asarray(acc_row)])

    def refresh_labels(self, client: int) -> None:
        """A store's validation set was replaced in place
        (`PredictionStore.refresh_validation`): re-upload its label row
        and mark EVERY slot dirty — including empty ones, whose cached
        acc seeds (`_zero_row_acc`) depend on the label-0 fraction — so
        the next flush rebuilds this client's statistics bit-identically
        to a from-scratch mirror of the refreshed store."""
        store = self.stores[client]
        labels = np.array(self.labels)   # device arrays view read-only
        row = np.full((self.v_max,), -1, np.int32)
        row[:store.v_pad] = store.labels
        labels[client] = row
        self.labels = jnp.asarray(labels)
        self._n_valid[client] = int((row >= 0).sum())
        nv = np.array(self.nv)
        nv[client] = max(int(self._n_valid[client]), 1)
        self.nv = jnp.asarray(nv)
        acc = np.array(self.acc)
        acc[client] = _zero_row_acc(row)
        self.acc = jnp.asarray(acc)
        self._dirty[client].update(range(self.capacity))

    # ---- incremental flush --------------------------------------------
    def _drain(self):
        """Per-client sorted dirty-slot groups (advancing OUR cursor over
        each store's dirty log — multi-consumer safe).
        Returns (groups [(client, slots)], n_distinct_dirty_slots)."""
        groups, n_dirty = [], 0
        for i, s in enumerate(self.stores):
            if s._dirty_clock > self._cursor[i]:
                self._dirty[i].update(
                    slot for slot, seq in s.dirty_seq.items()
                    if seq > self._cursor[i])
                self._cursor[i] = s._dirty_clock
            slots = sorted(self._dirty[i])
            self._dirty[i].clear()
            if slots:
                groups.append((i, slots))
                n_dirty += len(slots)
        return groups, n_dirty

    def _staging(self, U: int) -> np.ndarray:
        """The host block for a U-row upload, kept across flushes. The
        runtime may read (and on the CPU alias) it after the upload call
        returns, so a block is rewritten only once the flush that read it
        has run: before reusing one, wait for the newest `S`, which every
        earlier flush precedes (each takes the last one's `S`)."""
        buf = self._stage.get(U)
        if buf is None:
            buf = self._stage[U] = np.empty(
                (U, self.v_max, self.n_classes), np.float32)
        else:
            self.S.block_until_ready()
        return buf

    def _flush_bucket(self, groups, R: int):
        """One donated scatter+recompute for all groups padded to width R
        (`groups` are distinct clients: the useful bytes are theirs).
        Each distinct dirty row is uploaded once, in a block of U =
        pow2(rows) rows, and `_expand` pads it on the device."""
        mx = self.metrics
        useful = sum(len(blk) * int(self._n_valid[c])
                     for c, blk in groups) * self.n_classes * 4
        with mx.span("store.flush.assemble"):
            G, K = len(groups), _pow2(len(groups))
            all_clients = (K == G == len(self.stores)
                           and all(g[0] == i for i, g in enumerate(groups)))
            U = _pow2(sum(len(blk) for _, blk in groups))
            rows = self._staging(U)
            rmask = np.zeros((U,), np.float32)
            cu = np.zeros((K,), np.int32)
            slots = np.zeros((K, R), np.int32)
            src = np.zeros((K, R), np.int32)
            i = 0
            for k, (c, blk) in enumerate(groups):
                s, n = self.stores[c], len(blk)
                cu[k] = c
                slots[k] = blk + [blk[-1]] * (R - n)
                src[k] = i + np.minimum(np.arange(R), n - 1)
                for j, slot in enumerate(blk):
                    rows[i + j, :s.v_pad] = s.preds[slot]
                rows[i:i + n, s.v_pad:] = 0.0
                rmask[i:i + n] = s.mask[blk]
                i += n
            # the group count is padded by repeating group 0
            cu[G:], slots[G:], src[G:] = cu[0], slots[0], src[0]
        host = (rows, rmask, src.reshape(-1), cu, slots)
        with mx.span("store.flush.upload"):
            dev = [jnp.asarray(a) for a in host]
        with mx.span("store.flush.launch"):
            self.preds, self.pnorm, self.masks, self.acc, self.S = _flush(
                self.preds, self.pnorm, self.masks, self.acc, self.S,
                self.labels, self.nv, *_expand(*dev[:3]), *dev[3:],
                all_clients=all_clients)
        uploaded = sum(a.nbytes for a in host)
        self.n_flushes += 1
        self.n_rows_uploaded += U
        self.n_rows_expanded += K * R
        self.n_bytes_uploaded += uploaded
        self.n_bytes_useful += useful
        if mx.enabled:
            mx.inc("store.upload_rows", U)
            mx.inc("store.expanded_rows", K * R)
            mx.inc("store.upload_bytes", uploaded)
            mx.inc("store.useful_bytes", useful)

    def flush(self):
        """Drain the dirty queues into donated scatter + stats updates.
        No-op (no jit launch) when nothing changed since the last flush.
        Returns the number of distinct dirty slots drained.

        Groups are BUCKETED by their own power-of-two slot width and each
        bucket launches one scatter (group count padded to a power of two
        by repeating — scatter and recompute are idempotent): a run still
        compiles O(log N · log M) flush variants and launches at most
        log M scatters per flush, but one bursty client (e.g. a fresh
        churn join with every slot dirty) no longer inflates the padded
        width of every other client's group."""
        mx = self.metrics
        with mx.span("store.flush"):
            with mx.span("store.flush.drain"):
                groups, n_dirty = self._drain()
            if not groups:
                return 0
            buckets = {}
            for g in groups:
                # floor the width at 2: an R=1 launch lowers to a matvec
                # whose fp reduction order differs from the R>=2 matmuls
                # (matmul widths are bit-stable across R and K), which
                # would break incremental-vs-one-shot bitwise stat parity
                buckets.setdefault(max(2, _pow2(len(g[1]))), []).append(g)
            for R in sorted(buckets):
                self._flush_bucket(buckets[R], R)
        self.n_rows_scattered += n_dirty
        return n_dirty

    # ---- batched reads ------------------------------------------------
    def gather(self, clients):
        """(preds, labels, masks, acc, S) for a client batch — a device
        `jnp.take` per buffer, no host restack. Call `flush()` first."""
        idx = jnp.asarray(np.asarray(clients, np.int32))
        return _gather(self.preds, self.labels, self.masks,
                       self.acc, self.S, idx)
