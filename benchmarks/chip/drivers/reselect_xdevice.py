"""Cross-device re-selection traffic: a fleet whose selection state is
split by client over the chips (`SelectionEngine(shards=...)`), bounded
stores that evict, and one `select` over the clients that received a
model. A closed loop, as `reselect.py`'s.

Mix parameters (traffic/<mix>.json):
  clients_per_batch  clients drawn uniformly per batch; each receives
                     models and is re-selected (an int)
  models_per_client  peer models each of them receives (an int)
  new_model_share    share of those that are a model the store does not
                     hold (a full store evicts its least-selected
                     unpinned slot for it); the rest are a newer version
                     of a peer model it holds, rewritten in place
  answers_sampled    how many (batch, client) answers of the window the
                     reference checks besides the checked clients' last

Set-up makes the writers' labels (`data/writers.py`), one bounded
`StreamingPredictionStore` per client (`store_slots`, own models
pinned, validation rows padded as `sim.build.build_world_stores` pads
them), and the engine sharded over min(`shards`, chips). It fills
every store with the client's own models, then its peers', until full,
flushing a few clients of every shard at a time; rows are made on the
device from the seed, as each model arrives, and none is kept besides
the stores. Then one batch per per-shard width the window can reach.

The driver keeps its own record of every slot: the model in it, the
seeded stream its rows came from, and the eviction policy's inputs
(selections counted from the answers, arrival times). Each add applies
the policy to the record (a held model is rewritten in place; a new one
takes the lowest free slot, or evicts the unpinned slot with the fewest
selections, then the oldest, then the lowest index; own models are
pinned); `slot_miss` counts the adds where the store chose another slot.
The reference's rows are made again from the seed and that record, not
read from the stores: at the end for the final state, and from the
record's copy at each sampled answer. It covers every client written in
the window and 64 others drawn from the seed over every shard. After
`snapshot`, `n`, `labels` and `rows` index those checked clients (what
`check` and `control.py` read).
"""
from __future__ import annotations

import inspect
import multiprocessing
import os
import warnings
from concurrent.futures import ProcessPoolExecutor
from functools import partial
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from chip import flops
from chip.data.writers import writer_val_labels
from chip.drivers import reselect
from chip.harness import now

CHUNK = 8           # clients of each shard filled and flushed at a time
UNWRITTEN = 64      # clients checked besides those written in the window
SUM = ("k_miss", "vote_miss", "front_miss", "answers_checked")
MEAN = ("S_diag_deficit", "S_diag_excess")


def _pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


@partial(jax.jit, static_argnames=("n_classes",))
def _make_rows(key, ids, labels, local, margin_local, margin_remote,
               n_classes: int):
    """(R, V·C): softmax of N(0,1) logits plus a true-class margin drawn
    per row (U[margin_local] for the client's own model, U[margin_remote]
    for a peer's), zero on padding; row i from stream fold_in(key, ids[i]).
    Flat, so that its copy to the host carries no padding of C to the
    device's 128 lanes."""
    valid = labels >= 0
    onehot = jax.nn.one_hot(jnp.maximum(labels, 0), n_classes) \
        * valid[..., None]

    def one(i, oh, ok, loc):
        k_m, k_z = jax.random.split(jax.random.fold_in(key, i))
        lo = jnp.where(loc, margin_local[0], margin_remote[0])
        hi = jnp.where(loc, margin_local[1], margin_remote[1])
        m = jax.random.uniform(k_m, (), minval=lo, maxval=hi)
        z = jax.random.normal(k_z, oh.shape, jnp.float32)
        return jax.nn.softmax(z + m * oh, axis=-1) * ok[:, None]
    return jax.vmap(one)(ids, onehot, valid, local).reshape(len(ids), -1)


class Workload(reselect.Workload):
    unit = "batch"

    def __init__(self, cfg: dict, mix: dict, seeds):
        from repro.core.bench import BenchEntry, StreamingPredictionStore
        from repro.core.engine import SelectionEngine
        from repro.core.nsga2 import NSGAConfig
        if "shards" not in inspect.signature(SelectionEngine).parameters:
            raise RuntimeError("this program's SelectionEngine takes no "
                               "shards: it cannot split a fleet over chips")
        t0 = now()
        self._entry = BenchEntry
        self.cfg = cfg
        # the check's limits under the name control.py reads them by
        cfg["limits"].setdefault("reselect", cfg["limits"][mix["driver"]])
        n, C, F = cfg["n_clients"], cfg["n_classes"], len(cfg["families"])
        self.n_fleet, self.n, self.F, self.C = n, n, F, C
        self.M = cfg["store_slots"]
        self.kc = min(int(mix["clients_per_batch"]), n)
        self.km = int(mix["models_per_client"])
        self.new_share = float(mix["new_model_share"])
        val = writer_val_labels(cfg)
        self.n_val = np.array([len(y) for y in val])
        stores = [StreamingPredictionStore(
            c, self.M, np.zeros((len(y), 1), np.float32), y, C)
            for c, y in enumerate(val)]
        self.v_pad = max(s.v_pad for s in stores)
        self.labels_all = np.full((n, self.v_pad), -1, np.int32)
        for c, y in enumerate(val):
            self.labels_all[c, :len(y)] = y
        self.labels = self.labels_all
        self.nsga = NSGAConfig(pop_size=cfg["pop_size"],
                               generations=cfg["generations"], k=cfg["k"],
                               p_mut=cfg["p_mut"], p_cross=cfg["p_cross"],
                               seed=cfg["nsga_seed"])
        self.shards = min(int(cfg["shards"]), len(jax.devices()))
        t_built = now()
        self.engine = SelectionEngine(stores, self.nsga, use_kernel=False,
                                      seed=seeds.engine, ensemble_k=cfg["k"],
                                      device_resident=True,
                                      shards=self.shards)
        self.stores = stores
        self.rng = seeds.rng("schedule")
        self.sample_rng = seeds.rng("sample")
        self.n_sample = int(mix.get("answers_sampled", 8))
        self.sampled = []          # reservoir of (client, result, rows)
        self.n_answers = 0
        self.written = set()       # clients written in the window
        self._key = jnp.asarray(seeds.rows_key)
        self._margins = [np.asarray(cfg["rows"][k], np.float32)
                         for k in ("margin_local", "margin_remote")]
        self._n_rows = 0           # rows made so far: each row's stream
        self._t = 0.0              # virtual time of the batches
        # the driver's record of each slot (-1: empty) and of what the
        # eviction policy ranks by
        self._gid = np.full((n, self.M), -1, np.int64)
        self._stream = np.full((n, self.M), -1, np.int64)
        self._hits = np.zeros((n, self.M), np.int64)
        self._last = np.zeros((n, self.M), np.float64)
        self._slot = [dict() for _ in range(n)]   # model id -> slot
        self.slot_miss = 0
        t1 = now()
        fill = self._fill(np.random.default_rng(cfg["data_seed"]))
        t2 = now()
        self._warm()
        # where set-up goes: labels and empty stores, the sharded engine,
        # the fill (waiting for rows, writing them into the stores,
        # flushing), the warm batches
        self.setup_phases_s = dict({"stores": t_built - t0,
                                    "engine": t1 - t_built, "fill": t2 - t1},
                                   **fill, warm=now() - t2)

    # ---- rows ---------------------------------------------------------
    def _launch(self, clients, gids, ids):
        """Rows from streams `ids` for model `gids[i]` on client
        `clients[i]`, on the device, their copy to the host started."""
        clients = np.asarray(clients)
        local = np.asarray(gids) // self.F == clients
        out = _make_rows(self._key, np.asarray(ids, np.uint32),
                         self.labels_all[clients], local, *self._margins,
                         n_classes=self.C)
        out.copy_to_host_async()
        return out

    def _make(self, clients, gids):
        """(device rows, stream ids) of fresh rows for `gids` on
        `clients`: each row from a stream of its own."""
        ids = np.arange(self._n_rows, self._n_rows + len(gids))
        self._n_rows += len(gids)
        return self._launch(clients, gids, ids), ids

    def _host(self, rows) -> np.ndarray:
        """(R, V, C) host rows of `_make`'s flat device rows."""
        return np.asarray(rows).reshape(len(rows), self.v_pad, self.C)

    def _add(self, c: int, gid: int, row: np.ndarray, stream: int):
        """Model `gid` reaches client c: into the program's store, and
        into the driver's record by the policy the store should follow."""
        fams = self.cfg["families"]
        got = self.stores[c].add(
            self._entry(model_id=gid, owner=gid // self.F,
                        family=fams[gid % self.F],
                        predict=reselect._no_forward),
            preds=row[:self.n_val[c]], t=self._t)
        want = self._expect_slot(c, gid)
        self.slot_miss += got != want
        if want is not None:
            self._gid[c, want], self._stream[c, want] = gid, stream
            self._last[c, want] = self._t

    def _expect_slot(self, c: int, gid: int):
        """The slot `gid` belongs in on client c: its own if held, else
        the lowest free one, else the unpinned slot with the fewest
        selections, then the oldest, then the lowest (its count and time
        start afresh); None when every slot is pinned."""
        held = self._slot[c]
        if gid in held:
            return held[gid]
        if len(held) < self.M:     # slots fill in order; an evicted
            slot = len(held)       # one is refilled at once
        else:
            cand = np.flatnonzero(self._gid[c] // self.F != c)
            if len(cand) == 0:
                return None
            order = np.lexsort((cand, self._last[c, cand],
                                self._hits[c, cand]))
            slot = int(cand[order[0]])
            del held[int(self._gid[c, slot])]
            self._hits[c, slot] = 0
        held[gid] = slot
        return slot

    # ---- set-up -------------------------------------------------------
    def _fill(self, rng):
        """Own models, then peers' (25 drawn per client from the data
        seed, each with every family), until the store is full; written
        and flushed CHUNK clients of every shard at a time."""
        n, F = self.n_fleet, self.F
        k = min(int(self.cfg["topology_k"]), n - 1)
        models = []
        for c in range(n):
            peers = rng.choice(n - 1, k, replace=False)
            peers += peers >= c
            gids = [c * F + f for f in range(F)]
            gids += [int(p) * F + f for p in peers for f in range(F)]
            models.append(gids[:self.M])
        n_loc = self.engine.device.n_local
        chunks = []
        for lo in range(0, n_loc, CHUNK):
            chunk = [s * n_loc + i for s in range(self.shards)
                     for i in range(lo, min(lo + CHUNK, n_loc))
                     if s * n_loc + i < n]
            chunks.append([(c, g) for c in chunk for g in models[c]])
        # the next chunk's rows are made and copied while this one's are
        # written into the stores and flushed
        ahead = self._make(*zip(*chunks[0]))
        spent = dict.fromkeys(("fill_rows", "fill_add", "fill_flush"), 0.0)
        self._chunk_rows = len(chunks[0])
        for i, pairs in enumerate(chunks):
            t0 = now()
            rows, ids = self._host(ahead[0]), ahead[1]
            if i + 1 < len(chunks):
                ahead = self._make(*zip(*chunks[i + 1]))
            t1 = now()
            for (c, g), row, i_row in zip(pairs, rows, ids):
                self._add(c, g, row, int(i_row))
            t2 = now()
            self.engine.device.flush()
            t3 = now()
            spent["fill_rows"] += t1 - t0
            spent["fill_add"] += t2 - t1
            spent["fill_flush"] += t3 - t2
        return spent

    def _widths(self) -> list:
        """Every per-shard width a batch of `kc` clients can have."""
        n_loc = self.engine.device.n_local
        lo = _pow2(-(-self.kc // self.shards))
        hi = _pow2(min(self.kc, n_loc))
        return [w for w in (2 ** i for i in range(32)) if lo <= w <= hi]

    def _warm(self):
        """One batch per per-shard width: its busiest shard holds
        min(width, clients per shard) of the batch, the rest spread
        over the other shards. Compiles every program the window runs."""
        n_loc = self.engine.device.n_local
        for w in self._widths():
            cap = min(w, n_loc)
            per = [list(range(s * n_loc, min((s + 1) * n_loc, self.n_fleet)))
                   for s in range(self.shards)]
            batch = per[0][:cap]
            rest = [c for s in range(1, self.shards) for c in per[s][:cap]]
            batch += rest[:self.kc - len(batch)]
            self._batch(sorted(batch))
        jax.block_until_ready((self.engine.device.acc, self.engine.device.S))

    def describe(self) -> dict:
        dev = self.engine.device
        return {"n_clients": self.n_fleet, "slots": self.M,
                "classes": self.C, "v_pad": self.v_pad,
                "v_max": int(self.n_val.max()),
                "v_min": int(self.n_val.min()),
                "clients_per_batch": self.kc,
                "models_per_client": self.km, "shards": self.shards,
                "clients_per_shard": dev.n_local,
                "widths_warmed": self._widths(),
                "evicted_in_setup": dev.n_rows_evicted,
                "store_shape": list(dev.preds.shape),
                "setup_phases_s": {k: round(v, 3) for k, v in
                                   self.setup_phases_s.items()}}

    # ---- window -------------------------------------------------------
    def _arrival(self, c: int) -> int:
        """The model id that reaches client c: a peer model it does not
        hold (share `new_share`, unless it holds the whole fleet), else
        a newer version of a peer model it holds."""
        held = self._slot[c]
        n_models = self.n_fleet * self.F
        if self.rng.random() < self.new_share and len(held) < n_models:
            while True:
                g = int(self.rng.integers(n_models))
                if g not in held:
                    return g
        peers = [g for g in held if g // self.F != c]
        return peers[int(self.rng.integers(len(peers)))]

    def _batch(self, clients, split: bool = False) -> dict:
        """Models reach `clients`, then one select over them."""
        engine = self.engine
        self._t += 1.0
        lanes0, pad0 = engine.n_lanes, engine.n_pad_lanes
        t0 = now()
        with jax.profiler.TraceAnnotation("rows_to_stores"):
            pairs = [(c, self._arrival(c)) for c in clients
                     for _ in range(self.km)]
            out, ids = self._make(*zip(*pairs))
            for (c, g), row, i_row in zip(pairs, self._host(out), ids):
                self._add(c, g, row, int(i_row))
        t1 = now()
        rec = {"rows_s": t1 - t0}
        if split:
            with jax.profiler.TraceAnnotation("flush"):
                engine.device.flush()
                jax.block_until_ready((engine.device.acc, engine.device.S))
            t2 = now()
            rec["flush_s"] = t2 - t1
            t1 = t2
        with jax.profiler.TraceAnnotation("ga"):
            results = engine.select(clients, t=self._t)
        t3 = now()
        for c, res in results.items():     # the policy's selection counts
            sel = res["chromosome"] > 0.5
            self._hits[c, sel] += 1
            self._last[c, sel] = self._t
        if split:
            rec["ga_s"] = t3 - t1
        rec["seconds"] = t3 - t0
        rec["flops"] = flops.reselect_batch(
            sum(int(self.n_val[c]) for c, _ in pairs),
            [int(self.n_val[c]) for c in clients], self.M, self.C,
            self.nsga.pop_size, self.nsga.generations)
        rec["lanes"] = engine.n_lanes - lanes0
        rec["pad_lanes"] = engine.n_pad_lanes - pad0
        rec["shards"] = self.shards
        rec["results"] = results
        return rec

    def step(self, split: bool) -> dict:
        clients = sorted(int(c) for c in self.rng.choice(
            self.n_fleet, self.kc, replace=False))
        self.written.update(clients)
        rec = self._batch(clients, split)
        self._sample(rec.pop("results"))
        return rec

    def _sample(self, results: dict):
        """Reservoir-sample (client, answer) pairs of the window from the
        seed, with the record of the slots each answer was computed on."""
        for c in sorted(results):
            self.n_answers += 1
            if len(self.sampled) < self.n_sample:
                slot = len(self.sampled)
                self.sampled.append(None)
            else:
                slot = int(self.sample_rng.integers(self.n_answers))
                if slot >= self.n_sample:
                    continue
            res = {k: np.array(results[c][k]) for k in
                   ("chromosome", "val_accuracy", "pareto_mask", "pop",
                    "objs")}
            self.sampled[slot] = (c, res, (self._stream[c].copy(),
                                           self._gid[c].copy()))

    # ---- after the window ---------------------------------------------
    def _checked(self) -> list:
        """The clients written in the window, and UNWRITTEN others drawn
        from the seed, an equal share from every shard."""
        n_loc = self.engine.device.n_local
        extra = []
        for s in range(self.shards):
            free = [c for c in range(s * n_loc, min((s + 1) * n_loc,
                                                    self.n_fleet))
                    if c not in self.written]
            take = min(len(free), UNWRITTEN // self.shards)
            extra += self.sample_rng.choice(free, take,
                                            replace=False).tolist()
        return sorted(self.written | set(int(c) for c in extra))

    def _remake(self, slots) -> list:
        """(M, n_val, C) host rows of each (client, streams, models) slot
        record, made again from the seed on the device (zero where a slot
        is empty), in calls of the fill's first chunk of rows, a shape
        set-up compiled."""
        M, R = self.M, self._chunk_rows
        clients = np.repeat([c for c, _, _ in slots], M)
        ids = np.concatenate([np.maximum(s, 0) for _, s, _ in slots])
        gids = np.concatenate([g for _, _, g in slots])
        pad = -len(ids) % R
        clients, ids, gids = (np.concatenate([x, np.repeat(x[:1], pad)])
                              for x in (clients, ids, gids))
        out = [np.empty((M, self.n_val[c], self.C), np.float32)
               for c, _, _ in slots]
        ahead = self._launch(clients[:R], gids[:R], ids[:R])
        for lo in range(0, len(ids), R):
            rows = self._host(ahead)
            hi = lo + R
            if hi < len(ids):
                ahead = self._launch(clients[hi:hi + R], gids[hi:hi + R],
                                     ids[hi:hi + R])
            for j in range(lo // M, min(-(-hi // M), len(slots))):
                a, b = max(lo, j * M), min(hi, (j + 1) * M)
                nv = self.n_val[slots[j][0]]
                out[j][a - j * M:b - j * M] = rows[a - lo:b - lo, :nv]
        for block, (_, streams, _) in zip(out, slots):
            block[streams < 0] = 0.0
        return out

    def snapshot(self) -> dict:
        """Host copies of what the check reads, indexed by checked client;
        the rows are made again from the driver's record."""
        dev = self.engine.device
        checked = self._checked()
        at = {c: j for j, c in enumerate(checked)}
        slots = [(c, self._stream[c], self._gid[c]) for c in checked]
        sampled = []
        for a in self.sampled:
            if a is None:
                continue
            c, res, (streams, gids) = a
            sampled.append((at[c], res, np.int64(len(slots))))
            slots.append((c, streams, gids))
        self._table = self._remake(slots)
        final = {at[c]: {k: np.array(r[k]) for k in
                         ("chromosome", "val_accuracy", "pareto_mask", "pop",
                          "objs")}
                 for c, r in self.engine.results.items() if c in at}
        self.n = len(checked)
        self.labels = [self.labels_all[c, :self.n_val[c]] for c in checked]
        return {"acc": np.asarray(dev.acc)[checked],
                "S": np.asarray(dev.S)[checked], "final": final,
                "variant": np.arange(len(checked)), "sampled": sampled,
                "clients": checked}

    def rows(self, c: int, variant) -> np.ndarray:
        return self._table[int(variant)]

    def check(self, snap: dict, limits: dict) -> list:
        """`reselect`'s comparisons, run on slices of the checked clients
        in forked worker processes (the reference is numpy on the host;
        ~20 ms a client) and merged: a gap is the largest over the
        slices, a miss count their sum, a diagonal shift their mean."""
        global _CHECKING
        n = self.n
        workers = min(os.cpu_count() or 1, 32, n // 16)
        cuts = np.linspace(0, n, max(1, 4 * workers) + 1).astype(int)
        _CHECKING = (self, snap, limits)
        try:
            if workers < 2:
                parts = [_check_slice(b) for b in zip(cuts[:-1], cuts[1:])]
            else:
                with warnings.catch_warnings():
                    # fork warns of the runtime's threads; the workers
                    # run numpy only
                    warnings.simplefilter("ignore")
                    ctx = multiprocessing.get_context("fork")
                    with ProcessPoolExecutor(workers, mp_context=ctx) as ex:
                        parts = list(ex.map(_check_slice,
                                            zip(cuts[:-1], cuts[1:])))
        finally:
            _CHECKING = None
        out = []
        for i, first in enumerate(parts[0][1]):
            vals = [(w, p[i]["value"]) for w, p in parts]
            if first["name"] in SUM:
                value = sum(v for _, v in vals)
            elif first["name"] in MEAN:
                value = float(sum(w * v for w, v in vals) / n)
            else:
                value = max(v for _, v in vals)
            out.append(dict(first, value=value))
        out.append({"name": "slot_miss", "value": int(self.slot_miss),
                    "limit": 0})
        return out


# (workload, snapshot, limits) of the check in progress: forked check
# workers read it from here instead of receiving it pickled
_CHECKING = None


def _check_slice(bounds):
    """(client count, `reselect`'s checks) of checked clients lo..hi-1."""
    from threadpoolctl import threadpool_limits
    wl, snap, limits = _CHECKING
    lo, hi = (int(b) for b in bounds)
    at = range(lo, hi)
    view = SimpleNamespace(n=hi - lo, labels=wl.labels[lo:hi], nsga=wl.nsga,
                           rows=lambda c, v: wl.rows(c + lo, v))
    sub = {"acc": snap["acc"][lo:hi], "S": snap["S"][lo:hi],
           "variant": snap["variant"][lo:hi],
           "final": {c - lo: r for c, r in snap["final"].items() if c in at},
           "sampled": [(c - lo, r, v) for c, r, v in snap["sampled"]
                       if c in at]}
    with threadpool_limits(1):
        return hi - lo, reselect.Workload.check(view, sub, limits)
