"""The unified selection engine: ONE batched selection path shared by the
synchronous round and the discrete-event asynchronous simulator, both
driven by the spec layer's `Experiment`.

The engine owns every client's `PredictionStore` and, by default, a
device-resident mirror of the whole fleet (`DeviceStoreBatch`,
DESIGN.md §7): stacked preds/labels/mask tensors live ON DEVICE next to
persistent per-client statistics `acc (N, M)` / `S (N, M, M)`. A select
drains the stores' dirty queues into one donated-buffer scatter that
touches only the changed rows, gathers the requested client batch with
`jnp.take` (no host restack), and answers with a single vmap-compiled
NSGA-II run over the CACHED statistics
(`selection.select_ensembles_from_stats`): per-client PRNG streams,
per-client model-slot masks (models that have not arrived yet simply stay
masked off), and — with use_kernel=True — one batched Pallas
`ensemble_fitness` launch per objective evaluation. With
`device_resident=False` the legacy restack path (host `stack_stores` +
full stats recompute) is kept for benchmarking.

Client batches are padded to the next power of two (by repeating the
first client) so the jitted program is compiled for O(log N) distinct
batch sizes no matter how the async event stream groups re-selections
(DESIGN.md §4).

With `shards > 1` the device batch is split by client over that many
devices (DESIGN.md §15): a select splits its clients by owner shard
(span `engine.select.split`), pads every shard to one power-of-two
width W (series `engine.select.shard_width`), and runs the gather, the
GA and the vote as ONE program over the shards, each on its own
clients; the results are fetched once. PRNG streams stay keyed by
client id, so an answer does not depend on the shard count. Every
select counts its GA lanes and the padded ones (`n_lanes`,
`n_pad_lanes`; counters `engine.select.lanes`, `engine.select.pad_lanes`),
and the front-peeling sweeps of its NSGA-II, the most over its real
lanes (`n_rank_sweeps`; counter `engine.select.rank_sweeps`): a lower
bound on the sweeps the lockstep loop ran, which shows how often the
truncated sort stops early. No per-client result carries it.
"""
from __future__ import annotations

from functools import lru_cache
from typing import Dict, Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.bench import stack_stores
from repro.core.device_store import (CLIENTS, DeviceStoreBatch, _gather,
                                     split_lanes)
from repro.core.nsga2 import NSGAConfig, client_keys
from repro.core.selection import (local_only_chromosome, select_ensembles,
                                  select_ensembles_from_stats)
from repro.obs.metrics import NULL_METRICS


@lru_cache(maxsize=None)
def _sharded_select(mesh, nsga: NSGAConfig, use_kernel: bool):
    """The gather and `select_ensembles_from_stats` as one program over
    the `clients` mesh: each shard selects for its own lanes, from its
    own resident buffers (`idx`: local client ids)."""
    def per_shard(preds, labels, masks, acc, S, idx, keys):
        preds, labels, masks, acc, S = _gather(preds, labels, masks, acc,
                                               S, idx)
        return select_ensembles_from_stats(
            acc, S, preds, labels, nsga, use_kernel=use_kernel, keys=keys,
            model_mask=masks)
    return jax.jit(jax.shard_map(per_shard, mesh=mesh,
                                 in_specs=(P(CLIENTS),) * 7,
                                 out_specs=P(CLIENTS), check_vma=False))


class SelectionEngine:
    """Batched, incremental ensemble selection over a fleet of stores."""

    def __init__(self, stores, nsga: NSGAConfig, use_kernel: bool = False,
                 seed: int = 0, ensemble_k: Optional[int] = None,
                 device_resident: bool = True, v_max: Optional[int] = None,
                 metrics=None, shards: int = 1):
        self.stores = list(stores)
        self.nsga = nsga
        self.use_kernel = use_kernel
        self.seed = seed
        self.ensemble_k = ensemble_k if ensemble_k is not None else max(nsga.k, 1)
        # pin the validation pad width globally: every batch, whatever its
        # membership, lowers to the same (B, M, V, C) jit signature family.
        # `v_max` provisions for clients that JOIN LATER with a wider
        # validation set — without it, a wider late joiner is rejected
        # (never silently truncated) by `add_store`/`select`.
        widest = max(s.v_pad for s in self.stores)
        if v_max is not None and v_max < widest:
            raise ValueError(
                f"engine v_max={v_max} narrower than an attached store's "
                f"v_pad={widest}")
        self._v_max = widest if v_max is None else v_max
        if shards > 1 and not device_resident:
            raise ValueError("shards > 1 splits the device-resident batch; "
                             "it needs device_resident=True")
        self.shards = shards
        self.device = (DeviceStoreBatch(self.stores, v_max=self._v_max,
                                        shards=shards)
                       if device_resident else None)
        self.metrics = metrics if metrics is not None else NULL_METRICS
        self.results: Dict[int, dict] = {}   # client -> last selection dict
        self._keys_cache: Dict[tuple, object] = {}  # batch -> PRNG streams
        self._n_selects = 0                  # the `select_id` of spans
        self.n_lanes = 0                     # GA lanes launched ...
        self.n_pad_lanes = 0                 # ... of which padding
        self.n_rank_sweeps = 0               # peeling sweeps, lane max

    @property
    def metrics(self):
        """The run's registry, shared with the device batch; settable
        after construction (a benchmark switches it on after warm-up)."""
        return self._metrics

    @metrics.setter
    def metrics(self, mx):
        self._metrics = mx
        if self.device is not None:
            self.device.metrics = mx

    # ---- membership ---------------------------------------------------
    def _check_width(self, store):
        if store.v_pad > self._v_max:
            raise ValueError(
                f"store v_pad={store.v_pad} exceeds the engine-wide pad "
                f"v_max={self._v_max}; construct the engine with "
                "v_max=<widest validation pad that can ever join> "
                "(a wider batch would silently truncate this client's "
                "validation set)")

    def add_store(self, store) -> int:
        """A client joining mid-run (churn): validate against the pinned
        engine-wide pad and mirror it into the device batch. Returns the
        new client index."""
        self._check_width(store)
        if self.device is not None:
            self.device.append_store(store)
        self.stores.append(store)
        return len(self.stores) - 1

    # ---- selection ----------------------------------------------------
    def min_models(self) -> int:
        """A client is selectable once it can fill an ensemble."""
        return max(1, self.nsga.k)

    def select(self, clients: Optional[Iterable[int]] = None,
               t: float = 0.0) -> Dict[int, dict]:
        """Run ONE vmapped NSGA-II over `clients` (default: all) and cache
        per-client results. Clients whose stores cannot fill an ensemble
        yet are skipped. Returns {client: selection dict}.

        `t` is the virtual time of the selection: it stamps the stores'
        contribution stats (`note_selection`) that drive streaming-store
        eviction, and each result snapshots the store's slot generations
        so `chromosome` can detect eviction underneath a cached answer."""
        if clients is None:
            clients = range(len(self.stores))
        ready = [c for c in clients if self.stores[c].n_present >= self.min_models()]
        if not ready:
            return {}
        for c in ready:
            self._check_width(self.stores[c])
        mx = self.metrics
        self._n_selects += 1
        with mx.span("engine.select", select_id=self._n_selects):
            # spans and series of the split only where there are shards
            smx = mx if self.shards > 1 else NULL_METRICS
            with smx.span("engine.select.split"):
                n_local = (len(self.stores) if self.device is None
                           else self.device.n_local)
                batch, lanes = split_lanes(ready, n_local, self.shards)
            if smx.enabled:
                smx.observe("engine.select.shard_width",
                            len(batch) // self.shards, t=t)
            self.n_lanes += len(batch)
            self.n_pad_lanes += len(batch) - len(ready)
            if mx.enabled:
                mx.observe("engine.ga_batch_width", len(batch), t=t)
                mx.inc("engine.select.lanes", len(batch))
                mx.inc("engine.select.pad_lanes", len(batch) - len(ready))
            with mx.span("engine.select.keys"):
                keys = self._batch_keys(batch)
            out = self._launch(batch, keys, t)
            # ONE device->host transfer per result key (a per-client
            # slicing loop over device arrays costs hundreds of tiny
            # transfers)
            with mx.span("engine.select.fetch"):
                host = {k: np.asarray(v) for k, v in out.items()}
            sweeps = int(host.pop("rank_sweeps")[lanes].max())
            self.n_rank_sweeps += sweeps
            if mx.enabled:
                mx.inc("engine.select.rank_sweeps", sweeps)
            with mx.span("engine.select.unpack"):
                fresh = {}
                for i, c in zip(lanes, ready):
                    res = {k: v[i] for k, v in host.items()}
                    res["slot_gen"] = self.stores[c].slot_gen.copy()
                    self.stores[c].note_selection(res["chromosome"] > 0.5, t)
                    self.results[c] = res
                    fresh[c] = res
        return fresh

    def _batch_keys(self, batch):
        """The batch's per-client PRNG streams, cached by composition
        (split over the shards with the lanes when sharded)."""
        keys = self._keys_cache.get(tuple(batch))
        if keys is None:
            if len(self._keys_cache) >= 128:   # churn can produce a new
                self._keys_cache.clear()       # composition per tick —
            keys = client_keys(self.seed, np.asarray(batch, np.uint32))
            if self.shards > 1:
                keys = jax.device_put(keys, NamedSharding(
                    self.device.mesh, P(CLIENTS)))
            self._keys_cache[tuple(batch)] = keys  # keep the cache bounded
        return keys

    def _launch(self, batch, keys, t: float):
        """Flush, gather and dispatch the batch's selection program; the
        results are still on the device."""
        mx = self.metrics
        if self.device is None:
            # legacy restack path: re-stack + re-derive everything
            with mx.span("engine.select.gather"):
                preds, labels, masks = stack_stores(self.stores, batch,
                                                    v_to=self._v_max)
            with mx.span("engine.select.launch"):
                return select_ensembles(
                    jnp.asarray(preds), jnp.asarray(labels), self.nsga,
                    use_kernel=self.use_kernel, keys=keys,
                    model_mask=jnp.asarray(masks))
        # incremental path: scatter only the dirty rows, then gather the
        # batch and its cached stats on device (DESIGN.md §7); a
        # whole-fleet batch in natural order is served from the resident
        # buffers directly (identity gather elided)
        dev = self.device
        if len(dev.stores) != len(self.stores):
            raise RuntimeError(
                "engine.stores grew without the device mirror — "
                "admit late joiners through engine.add_store()")
        n_dirty = dev.flush()
        if mx.enabled:
            mx.observe("engine.flush_dirty_slots", n_dirty, t=t)
        if self.shards > 1:
            with mx.span("engine.select.gather"):
                idx = dev._put(np.asarray(batch, np.int32) % dev.n_local)
            with mx.span("engine.select.launch"):
                return _sharded_select(dev.mesh, self.nsga,
                                       self.use_kernel)(
                    dev.preds, dev.labels, dev.masks, dev.acc, dev.S, idx,
                    keys)
        with mx.span("engine.select.gather"):
            if batch == list(range(len(self.stores))):
                preds, labels, masks, acc, S = (dev.preds, dev.labels,
                                                dev.masks, dev.acc, dev.S)
            else:
                preds, labels, masks, acc, S = dev.gather(batch)
        with mx.span("engine.select.launch"):
            return select_ensembles_from_stats(
                acc, S, preds, labels, self.nsga,
                use_kernel=self.use_kernel, keys=keys,
                model_mask=masks)

    def refresh_validation(self, c: int, x_val, y_val, preds) -> None:
        """Serving-time drift refresh (DESIGN.md §14): swap client c's
        validation set in place and keep the device mirror coherent —
        the label row re-uploads and every slot goes dirty, so the next
        flush rebuilds the cached acc/S statistics against the shifted
        world. The client's cached selection result is intentionally
        KEPT: the resident ensemble keeps serving (that staleness is
        exactly what the serving monitor measures) until a re-selection
        replaces it."""
        store = self.stores[c]
        self._check_width(store)
        store.refresh_validation(x_val, y_val, preds)
        if self.device is not None:
            self.device.refresh_labels(c)

    # ---- serving ------------------------------------------------------
    @staticmethod
    def _stale(store, res, chrom: np.ndarray) -> bool:
        """Does this cached chromosome reference a slot that was evicted
        (mask dropped) or remapped (generation bumped) since selection?"""
        sel = chrom > 0.5
        if not store.mask[sel].all():
            return True
        gen = res.get("slot_gen")
        return gen is not None and bool(
            (store.slot_gen[sel] != gen[sel]).any())

    def chromosome(self, c: int) -> np.ndarray:
        """The client's current ensemble, falling back to the local-only
        chromosome (negative-transfer safety valve) when no selection has
        run yet, the selected mask is empty, or — streaming stores — a
        selected slot was evicted/remapped since the selection ran (the
        slot-generation snapshot no longer matches the store)."""
        store = self.stores[c]
        res = self.results.get(c)
        chrom = None if res is None else np.asarray(res["chromosome"])
        if chrom is not None and self._stale(store, res, chrom):
            chrom = None
        if chrom is None or (chrom > 0.5).sum() == 0:
            present = store.mask.astype(np.float32)
            chrom = np.asarray(local_only_chromosome(
                jnp.asarray(store.is_local() & store.mask), self.ensemble_k))
            chrom = chrom * present
        return chrom

    def serve(self, c: int, x: np.ndarray):
        """Masked lazy test-set serving: fetch only the selected members'
        predictions, mean-prob vote. Returns (vote (N, C), chromosome)."""
        store = self.stores[c]
        chrom = self.chromosome(c)
        mask = chrom > 0.5
        probs = store.predictions(x, mask=mask)  # zeros where masked off
        vote = (chrom[:, None, None] * probs).sum(0) / max(1, int(mask.sum()))
        return vote, chrom
