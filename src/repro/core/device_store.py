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
`flush/gram`, `gather`) in their op names. `n_rows_evicted`
(`store.evicted_rows`) counts the stores' evictions each flush carries
to the device.

Sharding (DESIGN.md §15): with `shards > 1` every buffer is split by
client over a 1-D `clients` mesh of the first `shards` devices, the
client axis padded to a multiple of the shard count; client `c` lives
on shard `c // n_local`. A flush routes each dirty group to its owner
(span `store.flush.route`, series `store.shard_rows`), pads the group
count and the row block to one power of two across the shards, and runs
`_expand` and `_flush` under `shard_map`: each shard receives its own
rows only and scatters them into its own clients. With `shards == 1`
the programs are the unsharded ones, unchanged.
"""
from __future__ import annotations

from functools import lru_cache, partial
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.objectives import F32
from repro.obs.metrics import NULL_METRICS


CLIENTS = "clients"      # the mesh axis the fleet's client axis is split on


def client_mesh(shards: int) -> Mesh:
    """A 1-D `clients` mesh over the first `shards` devices."""
    devices = jax.devices()
    if shards > len(devices):
        raise ValueError(
            f"shards={shards} needs {shards} devices, but JAX sees "
            f"{len(devices)} ({devices[0].platform}); lower "
            "SelectionSpec.shards or run on a host with more chips")
    return Mesh(np.array(devices[:shards]), (CLIENTS,))


def _pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def split_lanes(clients, n_local: int, shards: int):
    """A select's GA lanes, shard-major: each shard's clients in order
    (client c on shard c // n_local), padded to W = pow2 of the busiest
    shard's count by repeating its first client (a shard with none
    repeats its own first client). Returns (lanes (shards·W,) client
    ids, the lane of each of `clients`). One shard: `clients` padded to
    a power of two by repeating the first."""
    per = [[] for _ in range(shards)]
    for i, c in enumerate(clients):
        per[c // n_local].append(i)
    W = _pow2(max(len(p) for p in per))
    lanes, pos = [], [0] * len(clients)
    for sh, p in enumerate(per):
        for i in p:
            pos[i] = len(lanes)
            lanes.append(clients[i])
        lanes += [clients[p[0]] if p else sh * n_local] * (W - len(p))
    return lanes, pos


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


@lru_cache(maxsize=None)
def sharded_programs(mesh: Mesh):
    """`_expand` and `_flush` as one program each over the `clients`
    mesh: every argument and result is split by its leading axis, and
    each shard runs the unsharded function on its own block."""
    spec = P(CLIENTS)

    def over(fn, n_in, n_out):
        return jax.shard_map(fn, mesh=mesh, in_specs=(spec,) * n_in,
                             out_specs=(spec,) * n_out, check_vma=False)

    def flush(*args, all_clients: bool = False):
        return over(partial(_flush, all_clients=all_clients), 11, 5)(*args)

    return (jax.jit(over(_expand, 3, 2)),
            jax.jit(flush, static_argnames=("all_clients",),
                    donate_argnums=(0, 1, 2, 3, 4)))


class DeviceStoreBatch:
    """Device mirror of a fleet of `PredictionStore`s + cached (acc, S).

    `shards > 1` splits every buffer by client over `client_mesh(shards)`
    (module docstring); `shards == 1` keeps them on the default device."""

    def __init__(self, stores, v_max: Optional[int] = None,
                 shards: int = 1):
        stores = list(stores)
        assert stores, "DeviceStoreBatch needs at least one store"
        cap = stores[0].capacity
        C = stores[0].n_classes
        self.v_max = max(s.v_pad for s in stores) if v_max is None else v_max
        self.capacity, self.n_classes = cap, C
        if shards < 1:
            raise ValueError(f"shards={shards}: a batch needs one or more")
        self.shards = shards
        self.n_local = -(-len(stores) // shards)   # clients per shard
        n_pad = self.n_local * shards
        self.mesh = client_mesh(shards) if shards > 1 else None
        self.stores: List = []
        self._dirty: List[set] = []        # per-client pending slot events
        self._cursor: List[int] = []       # per-client dirty-log position
        self._evicted: List[int] = []      # per-client eviction count seen
        self.n_flushes = 0
        self.n_rows_scattered = 0          # perf counters (bench/DESIGN §7)
        self.n_rows_uploaded = 0           # rows of the uploaded blocks
        self.n_rows_expanded = 0           # ... after the K/R padding
        self.n_bytes_uploaded = 0          # host->device bytes of flushes
        self.n_bytes_useful = 0            # ... that are real dirty rows
        self.n_rows_evicted = 0            # evictions the flushes carried
        self.metrics = NULL_METRICS        # the engine's registry (§11)
        self._stage = {}                   # U -> host upload block
        labels = np.full((n_pad, self.v_max), -1, np.int32)
        self.preds = self._zeros((n_pad, cap, self.v_max, C))
        self.pnorm = self._zeros(self.preds.shape)  # normalized mirror
        self.masks = self._zeros((n_pad, cap))
        self.S = self._zeros((n_pad, cap, cap))
        for i, s in enumerate(stores):
            self._attach(s, labels[i])
        self.labels = self._put(labels)
        # valid samples per client on the host (the useful-bytes count),
        # and in fp32 on device, the shared denominator of acc and S
        self._n_valid = (labels >= 0).sum(1)
        self.nv = self._put(np.maximum(self._n_valid, 1)
                            .astype(np.float32))
        acc0 = np.stack([np.full((cap,), _zero_row_acc(labels[i]), np.float32)
                         for i in range(n_pad)])
        self.acc = self._put(acc0)

    # ---- placement ----------------------------------------------------
    def _put(self, a: np.ndarray):
        """A host array on the batch's devices: split by its leading
        (client) axis over the mesh when sharded."""
        if self.mesh is None:
            return jnp.asarray(a)
        return jax.device_put(a, NamedSharding(self.mesh, P(CLIENTS)))

    def _zeros(self, shape):
        """A float32 zero buffer, made in place on the batch's devices
        (a sharded one never exists whole on one device)."""
        if self.mesh is None:
            return jnp.zeros(shape, jnp.float32)
        return jax.jit(lambda: jnp.zeros(shape, jnp.float32),
                       out_shardings=NamedSharding(self.mesh, P(CLIENTS)))()

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
        self._evicted.append(store.evictions)

    def append_store(self, store):
        """Grow the fleet by one client (churn join). The device buffers
        are reallocated with one extra row; the newcomer's slots flush on
        the next `flush()`. A sharded batch's client axis is fixed."""
        if self.mesh is not None:
            raise NotImplementedError(
                f"append_store on a batch sharded over {self.shards} "
                "devices: its client axis is fixed at build time (padded "
                "to a multiple of the shard count); build the engine with "
                "every client, or with shards=1")
        self.n_local += 1
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
        self.labels = self._put(labels)
        self._n_valid[client] = int((row >= 0).sum())
        nv = np.array(self.nv)
        nv[client] = max(int(self._n_valid[client]), 1)
        self.nv = self._put(nv)
        acc = np.array(self.acc)
        acc[client] = _zero_row_acc(row)
        self.acc = self._put(acc)
        self._dirty[client].update(range(self.capacity))

    # ---- incremental flush --------------------------------------------
    def _drain(self):
        """Per-client sorted dirty-slot groups (advancing OUR cursor over
        each store's dirty log — multi-consumer safe), counting the
        evictions they carry into `n_rows_evicted`.
        Returns (groups [(client, slots)], n_distinct_dirty_slots)."""
        groups, n_dirty = [], 0
        for i, s in enumerate(self.stores):
            if s._dirty_clock > self._cursor[i]:
                self._dirty[i].update(
                    slot for slot, seq in s.dirty_seq.items()
                    if seq > self._cursor[i])
                self._cursor[i] = s._dirty_clock
                self.n_rows_evicted += s.evictions - self._evicted[i]
                self._evicted[i] = s.evictions
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
        pow2(rows) rows, and `_expand` pads it on the device. Sharded,
        each shard gets a block of its own rows, K and U are the largest
        over the shards, and a shard with nothing dirty pads with a
        client index past its last, whose scatters are dropped."""
        mx = self.metrics
        useful = sum(len(blk) * int(self._n_valid[c])
                     for c, blk in groups) * self.n_classes * 4
        # the groups of each shard as (local client, client, slots);
        # spans and series only where there is more than one shard
        rmx = mx if self.mesh is not None else NULL_METRICS
        with rmx.span("store.flush.route"):
            per = [[] for _ in range(self.shards)]
            for c, blk in groups:
                per[c // self.n_local].append((c % self.n_local, c, blk))
            if rmx.enabled:
                for sh, gs in enumerate(per):
                    rmx.observe("store.shard_rows",
                                sum(len(blk) for *_, blk in gs), shard=sh)
        with mx.span("store.flush.assemble"):
            n_sh, n_loc = self.shards, self.n_local
            K = _pow2(max(len(gs) for gs in per))
            U = _pow2(max(max(1, sum(len(blk) for *_, blk in gs))
                          for gs in per))
            all_clients = all(
                len(gs) == K == n_loc
                and all(g[0] == i for i, g in enumerate(gs)) for gs in per)
            rows = self._staging(n_sh * U)
            rmask = np.zeros((n_sh * U,), np.float32)
            cu = np.zeros((n_sh * K,), np.int32)
            slots = np.zeros((n_sh * K, R), np.int32)
            src = np.zeros((n_sh * K, R), np.int32)
            for sh, gs in enumerate(per):
                g0, i = sh * K, 0
                for k, (lc, c, blk) in enumerate(gs):
                    s, n, r = self.stores[c], len(blk), sh * U + i
                    cu[g0 + k] = lc
                    slots[g0 + k] = blk + [blk[-1]] * (R - n)
                    src[g0 + k] = i + np.minimum(np.arange(R), n - 1)
                    for j, slot in enumerate(blk):
                        rows[r + j, :s.v_pad] = s.preds[slot]
                    rows[r:r + n, s.v_pad:] = 0.0
                    rmask[r:r + n] = s.mask[blk]
                    i += n
                if gs:
                    # the group count is padded by repeating group 0
                    G = len(gs)
                    cu[g0 + G:g0 + K] = cu[g0]
                    slots[g0 + G:g0 + K] = slots[g0]
                    src[g0 + G:g0 + K] = src[g0]
                else:
                    cu[g0:g0 + K] = n_loc
                    rows[sh * U] = 0.0
        host = (rows, rmask, src.reshape(-1), cu, slots)
        with mx.span("store.flush.upload"):
            dev = [self._put(a) for a in host]
        with mx.span("store.flush.launch"):
            expand, flush = ((_expand, _flush) if self.mesh is None
                             else sharded_programs(self.mesh))
            self.preds, self.pnorm, self.masks, self.acc, self.S = flush(
                self.preds, self.pnorm, self.masks, self.acc, self.S,
                self.labels, self.nv, *expand(*dev[:3]), *dev[3:],
                all_clients=all_clients)
        uploaded = sum(a.nbytes for a in host)
        self.n_flushes += 1
        self.n_rows_uploaded += n_sh * U
        self.n_rows_expanded += n_sh * K * R
        self.n_bytes_uploaded += uploaded
        self.n_bytes_useful += useful
        if mx.enabled:
            mx.inc("store.upload_rows", n_sh * U)
            mx.inc("store.expanded_rows", n_sh * K * R)
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
                evicted = self.n_rows_evicted
                groups, n_dirty = self._drain()
                if mx.enabled and self.n_rows_evicted > evicted:
                    mx.inc("store.evicted_rows",
                           self.n_rows_evicted - evicted)
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
        `jnp.take` per buffer, no host restack. Call `flush()` first.
        Unsharded batches only: a sharded select gathers on each shard,
        inside the engine's sharded program."""
        idx = jnp.asarray(np.asarray(clients, np.int32))
        return _gather(self.preds, self.labels, self.masks,
                       self.acc, self.S, idx)
