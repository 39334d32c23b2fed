"""Spec -> concrete objects: stock component builders, world builders,
and the image worlds' local training and store exchange.

Importing this module registers the stock components:

  transport:  "gossip"                    (p2p.GossipTransport)
  gossip:     "push", "push_pull"         (p2p.GossipProtocol)
  churn:      "lognormal"                 (p2p.ChurnSchedule, FLGo-style)
  repair:     "anti_entropy"              (p2p.AntiEntropyRepair)
  train_cost: "affine", "constant"        (virtual training durations)
  sizer:      "prediction_matrix", "checkpoint"  (transport pricing)

Each builder receives `(params, ctx)`; `build_network` assembles the
whole p2p stack in dependency order (topology -> churn -> gossip ->
transport -> repair) and injects the experiment seed into any component
whose params omit one — the spec's seed-completeness contract.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import numpy as np

from repro.core.bench import (BenchEntry, PredictionStore,
                              StreamingPredictionStore)
from repro.fl.client import (ClientData, predict_probs,
                             predict_probs_batched, train_local_model)
from repro.fl.topology import make_topology
from repro.models.cnn import CNNConfig, n_params
from repro.obs import probes as _obs_probes
from repro.p2p.churn import ChurnSchedule
from repro.p2p.params import check_params
from repro.p2p.gossip import GossipProtocol
from repro.p2p.repair import AntiEntropyRepair
from repro.p2p.transport import (GossipTransport, checkpoint_bytes,
                                 prediction_matrix_bytes)
from repro.sim.registry import build as build_component
from repro.sim.registry import register
from repro.sim.spec import (ComponentSpec, DataSpec, ExperimentSpec,
                            TrainSpec)

# ---- stock train-cost models ------------------------------------------


@register("train_cost", "affine")
def _affine_cost(params: dict, ctx: dict):
    """duration(c, m) = base + slope * m — the legacy drivers' default."""
    check_params(params, ("base", "slope"), "train_cost[affine]")
    base = float(params.get("base", 1.0))
    slope = float(params.get("slope", 0.3))
    return lambda c, m: base + slope * m


@register("train_cost", "constant")
def _constant_cost(params: dict, ctx: dict):
    check_params(params, ("base",), "train_cost[constant]")
    base = float(params.get("base", 1.0))
    return lambda c, m: base


# ---- stock message sizers ---------------------------------------------


@register("sizer", "prediction_matrix")
def _sizer_prediction(params: dict, ctx: dict):
    """The paper's §III-A low-storage exchange unit. Dimensions default
    to the world's (n_val, n_classes) from the build context."""
    check_params(params, ("n_val", "n_classes", "bytes_per_value"),
                 "sizer[prediction_matrix]")
    nb = prediction_matrix_bytes(
        int(params.get("n_val", ctx["n_val"])),
        int(params.get("n_classes", ctx["n_classes"])),
        int(params.get("bytes_per_value", 4)))
    return lambda src, dst, key: nb


@register("sizer", "checkpoint")
def _sizer_checkpoint(params: dict, ctx: dict):
    """The naive full-parameter-vector exchange (the cost baseline)."""
    check_params(params, ("n_params", "bytes_per_value"),
                 "sizer[checkpoint]")
    nb = checkpoint_bytes(int(params.get("n_params", 250_000)),
                          int(params.get("bytes_per_value", 4)))
    return lambda src, dst, key: nb


# ---- stock p2p components ---------------------------------------------


@register("transport", "gossip")
def _transport_gossip(params: dict, ctx: dict):
    sizer = ComponentSpec.of(params.pop("sizer", "prediction_matrix"),
                             "transport.sizer")
    size_fn = build_component("sizer", sizer, ctx)
    return GossipTransport.from_params(params, ctx["n_clients"], size_fn)


@register("gossip", "push")
def _gossip_push(params: dict, ctx: dict):
    return GossipProtocol.from_params("push", params, ctx["neighbors"],
                                      churn=ctx.get("churn"))


@register("gossip", "push_pull")
def _gossip_push_pull(params: dict, ctx: dict):
    return GossipProtocol.from_params("push_pull", params,
                                      ctx["neighbors"],
                                      churn=ctx.get("churn"))


@register("churn", "lognormal")
def _churn_lognormal(params: dict, ctx: dict):
    return ChurnSchedule.from_params(params, ctx["n_clients"])


@register("repair", "anti_entropy")
def _repair_anti_entropy(params: dict, ctx: dict):
    gossip = ctx.get("gossip")
    if gossip is None:
        raise ValueError("the anti_entropy repair component requires a "
                         "gossip component in network.gossip")
    return AntiEntropyRepair.from_params(params, gossip,
                                         churn=ctx.get("churn"))


# ---- simulator backends -----------------------------------------------


@register("backend", "event")
def _backend_event(params: dict, ctx: dict):
    """The event-granular heap loop (fl.scheduler.simulate_async) — the
    golden reference every other backend is validated against."""
    check_params(params, (), "backend[event]")
    return lambda exp: exp._run_async_event()


@register("backend", "compiled")
def _backend_compiled(params: dict, ctx: dict):
    """The jitted tick-stepped array world (repro.sim.compiled) for
    10k-100k-client dissemination studies; `tick` defaults to the
    transport base latency (1-tick hops)."""
    check_params(params, ("tick", "chunk_ticks", "max_ticks",
                          "key_block"), "backend[compiled]")
    kw = {k: params[k] for k in params}

    def run(exp):
        from repro.sim.compiled import run_compiled
        return run_compiled(exp, **kw)
    return run


# ---- fault injectors + admission (DESIGN.md §12) ----------------------
# Imported lazily inside the builders: repro.faults pulls numpy-heavy
# poisoning code no fault-free run needs at import time.


@register("fault", "byzantine")
def _fault_byzantine(params: dict, ctx: dict):
    from repro.faults import ByzantineFault
    return ByzantineFault.from_params(params, ctx["n_clients"])


@register("fault", "corruption")
def _fault_corruption(params: dict, ctx: dict):
    from repro.faults import CorruptionFault
    return CorruptionFault.from_params(params, ctx["n_clients"])


@register("fault", "crash_restart")
def _fault_crash_restart(params: dict, ctx: dict):
    from repro.faults import CrashRestartFault
    return CrashRestartFault.from_params(params, ctx["n_clients"])


@register("fault", "partition")
def _fault_partition(params: dict, ctx: dict):
    from repro.faults import PartitionFault
    return PartitionFault.from_params(params, ctx["n_clients"])


@register("admission", "validation_gate")
def _admission_validation_gate(params: dict, ctx: dict):
    """Returns the CONFIG, not the controller: the gates need the built
    stores (labels, class counts), which only the experiment driver
    holds — it wraps this in an AdmissionController."""
    from repro.faults import AdmissionConfig
    from repro.p2p.params import config_from_params
    return config_from_params(AdmissionConfig, params,
                              "admission[validation_gate]")


def build_faults(spec: ExperimentSpec, n_clients: int):
    """Aggregate the spec's fault injectors into one FaultController
    (None when no injectors are declared). `FaultSpec.seed` overrides the
    experiment seed for every injector whose params omit one."""
    fa = spec.faults
    if not fa.injectors:
        return None
    from repro.faults import FaultController
    base = fa.seed if fa.seed is not None else spec.seed
    ctx = {"n_clients": n_clients, "seed": base, "spec": spec}
    injectors = [build_component("fault", _seeded(cs, base), ctx)
                 for cs in fa.injectors]
    return FaultController(injectors, n_clients)


# ---- serving: traffic + drift (DESIGN.md §14) -------------------------
# Imported lazily like the fault injectors: repro.serve is dead weight
# for any run without a serve section.


@register("traffic", "poisson")
def _traffic_poisson(params: dict, ctx: dict):
    from repro.serve import PoissonTraffic
    return PoissonTraffic.from_params(params, ctx["n_clients"])


@register("traffic", "bursty")
def _traffic_bursty(params: dict, ctx: dict):
    from repro.serve import BurstyTraffic
    return BurstyTraffic.from_params(params, ctx["n_clients"])


@register("drift", "label_shift")
def _drift_label_shift(params: dict, ctx: dict):
    from repro.serve import LabelShiftDrift
    return LabelShiftDrift.from_params(params, ctx["n_clients"])


@register("drift", "covariate_shift")
def _drift_covariate_shift(params: dict, ctx: dict):
    from repro.serve import CovariateShiftDrift
    return CovariateShiftDrift.from_params(params, ctx["n_clients"])


def build_serving(spec: ExperimentSpec, n_clients: int, stores, engine,
                  query_pools=None):
    """Assemble the spec's serve section into one ServingEngine (None
    when no traffic component is declared). `ServeSpec.seed` overrides
    the experiment seed for the traffic/drift components whose params
    omit one — the same seed-completeness contract as build_faults."""
    sv = spec.serve
    if sv.traffic is None:
        return None
    from repro.serve import ServeConfig, ServingEngine
    base = sv.seed if sv.seed is not None else spec.seed
    ctx = {"n_clients": n_clients, "seed": base, "spec": spec}
    traffic = build_component("traffic", _seeded(sv.traffic, base), ctx)
    drifts = [build_component("drift", _seeded(cs, base), ctx)
              for cs in sv.drift]
    cfg = ServeConfig(
        policy=sv.policy, monitor=sv.monitor, window=sv.window,
        threshold=sv.threshold, debounce=sv.debounce,
        service_time=sv.service_time, des_k=sv.des_k,
        des_neighbors=sv.des_neighbors, seed=base)
    return ServingEngine(cfg, traffic, drifts, n_clients=n_clients,
                         n_classes=spec.data.n_classes, stores=stores,
                         engine=engine, query_pools=query_pools)


# ---- observability sinks ------------------------------------------------
# The builders live in repro.obs.probes (which must stay importable from
# the p2p/core layers without touching repro.sim); registration happens
# here with the rest of the stock set.
register("sink", "metrics_json")(_obs_probes.sink_metrics_json)
register("sink", "perfetto")(_obs_probes.sink_perfetto)


# ---- network stack assembly -------------------------------------------


def _seeded(cspec: Optional[ComponentSpec],
            seed: int) -> Optional[ComponentSpec]:
    """Inject the experiment seed into a component whose params omit one
    (without mutating the spec)."""
    if cspec is None or "seed" in cspec.params:
        return cspec
    return ComponentSpec(cspec.name, {**cspec.params, "seed": seed})


def build_network(spec: ExperimentSpec, n_clients: int,
                  n_val: Optional[int] = None,
                  injected: Optional[Dict[str, object]] = None
                  ) -> Dict[str, object]:
    """Assemble the p2p stack a spec describes. Returns a dict with
    `neighbors`, `transport`, `gossip`, `churn`, `repair`, `train_cost`
    (absent layers are None); the scheduler consumes them directly.

    `injected` maps slot names to caller-built collaborators
    (`Experiment`'s keyword overrides). An injected instance takes its slot AND
    participates in the build context, so spec-built dependents wire
    against the object that will actually run — a spec-declared repair
    component around an injected gossip must reconcile THAT gossip's
    version vectors, never an orphaned spec-built twin."""
    net = spec.network
    injected = injected or {}
    ctx: Dict[str, object] = {
        "n_clients": n_clients,
        "n_val": spec.data.n_val if n_val is None else n_val,
        "n_classes": spec.data.n_classes,
        "seed": spec.seed,
        "spec": spec,
    }
    ctx["neighbors"] = make_topology(net.topology, n_clients,
                                     k=net.topology_k, seed=spec.seed,
                                     beta=net.topology_beta)

    def slot(kind, name, cspec, seeded=True):
        if injected.get(name) is not None:
            ctx[name] = injected[name]
        else:
            ctx[name] = build_component(
                kind, _seeded(cspec, spec.seed) if seeded else cspec, ctx)

    slot("churn", "churn", net.churn)
    slot("gossip", "gossip", net.gossip)
    slot("transport", "transport", net.transport)
    slot("repair", "repair", net.repair)
    # train-cost models are deterministic functions — no seed to inject
    slot("train_cost", "train_cost", spec.schedule.train_cost,
         seeded=False)
    return ctx


# ---- worlds -----------------------------------------------------------


def build_client_datasets(data: DataSpec, default_seed: int):
    """kind="synthetic_images": non-IID image clients, the paper's
    protocol (class-conditional synthetic images, Dirichlet(alpha) label
    skew, 70/15/15 per-client splits)."""
    from repro.data import (dirichlet_partition, make_synthetic_images,
                            split_train_val_test)
    seed = data.seed if data.seed is not None else default_seed
    split_seed = data.split_seed if data.split_seed is not None \
        else seed + 1
    ds = make_synthetic_images(data.n_samples, data.n_classes,
                               size=data.image_size,
                               channels=data.channels, seed=seed)
    parts = dirichlet_partition(ds.y, data.n_clients, data.alpha,
                                seed=seed)
    datasets = []
    for ix in parts:
        tr, va, te = split_train_val_test(ix, seed=split_seed)
        datasets.append(ClientData(ds.x[tr], ds.y[tr], ds.x[va], ds.y[va],
                                   ds.x[te], ds.y[te]))
    return datasets


def build_prediction_world(data: DataSpec, default_seed: int
                           ) -> Tuple[dict, dict]:
    """kind="prediction_world": per-client labels and quality-
    parameterized prediction matrices — local models better than remote
    on average, no CNN training needed. Returns (labels, mats) with
    labels[c] = (V,) int labels and mats[(c, global_model_id)] = (V, C)
    row-normalized probabilities. V is `n_val`, or with
    partition="writers" each writer's validation split."""
    n, mpc = data.n_clients, data.models_per_client
    V, C = data.n_val, data.n_classes
    seed = data.seed if data.seed is not None else default_seed
    rng = np.random.default_rng(seed)
    if data.partition == "writers":
        from repro.data import split_train_val_test, writers_partition
        split_seed = data.split_seed if data.split_seed is not None \
            else seed + 1
        y, parts = writers_partition(n, C, *data.samples_per_writer,
                                     seed=seed)
        labels = {c: y[split_train_val_test(ix, seed=split_seed)[1]]
                  for c, ix in enumerate(parts)}
    else:
        labels = {c: rng.integers(0, C, V) for c in range(n)}
    mats = {}
    for c in range(n):
        V = len(labels[c])
        for owner in range(n):
            for m in range(mpc):
                q = rng.uniform(*data.quality_local) if owner == c \
                    else rng.uniform(*data.quality_remote)
                correct = rng.random(V) < q
                pred = np.where(correct, labels[c],
                                (labels[c] + 1 +
                                 rng.integers(0, C - 1, V)) % C)
                out = np.full((V, C), 0.05, np.float32)
                out[np.arange(V), pred] = 0.8
                mats[(c, owner * mpc + m)] = out / out.sum(1, keepdims=True)
    return labels, mats


def build_world_stores(data: DataSpec, labels: dict,
                       store_capacity: Optional[int]):
    """Empty (streaming) stores for a prediction world: bounded iff the
    capacity is below the global model count (mirrors `empty_stores` for
    the trainingless world)."""
    n, mpc, C = data.n_clients, data.models_per_client, data.n_classes
    full = n * mpc
    cap = full if store_capacity is None else store_capacity
    x_val = [np.zeros((len(labels[c]), 2), np.float32) for c in range(n)]
    if cap >= full:  # slot-aligned unbounded store, one slot per model
        return [PredictionStore(c, full, x_val[c], labels[c], C)
                for c in range(n)]
    return [StreamingPredictionStore(c, cap, x_val[c], labels[c], C)
            for c in range(n)]


# ---- image worlds: local training and the exchange ---------------------


def train_all_clients(datasets, train: TrainSpec, n_classes: int,
                      seed: int):
    """Local training: every client trains each family of `train`.
    Returns ({(client, family): (params, val_acc)}, ccfg)."""
    models = {}
    ccfg = CNNConfig(n_classes=n_classes, width=train.width,
                     in_channels=datasets[0].x_tr.shape[-1])
    for c, data in enumerate(datasets):
        for fi, fam in enumerate(train.families):
            params, va, _ = train_local_model(
                fam, ccfg, seed * 10007 + c * 101 + fi, data, lr=train.lr,
                batch=train.batch, max_epochs=train.max_epochs,
                patience=train.patience)
            models[(c, fam)] = (params, va)
    return models, ccfg


def make_entry(owner: int, fam: str, fam_idx: int, models, ccfg,
               n_families: int) -> BenchEntry:
    params, _ = models[(owner, fam)]
    # carrying (params, ccfg) lets the store serve same-family members
    # through one vmapped multi-model forward (bench.predictions)
    return BenchEntry(
        model_id=owner * n_families + fam_idx, owner=owner, family=fam,
        predict=(lambda x, f=fam, p=params: predict_probs(f, ccfg, p, x)),
        n_params=n_params(params), params=params, ccfg=ccfg)


def empty_stores(datasets, n_families: int, n_classes: int,
                 store_capacity: Optional[int]):
    """Slot-aligned stores: slot owner*F+fam_idx on every client, padded
    to one common validation width so all stacks share a jit signature.
    With `store_capacity` set (and smaller than the global model count)
    each client gets a bounded streaming store with contribution-aware
    eviction instead (DESIGN.md §6)."""
    full_capacity = len(datasets) * n_families
    v_max = max(len(d.y_va) for d in datasets)
    if store_capacity is not None and store_capacity < full_capacity:
        return [StreamingPredictionStore(c, store_capacity, d.x_va,
                                         d.y_va, n_classes, v_pad=v_max)
                for c, d in enumerate(datasets)]
    return [PredictionStore(c, full_capacity, d.x_va, d.y_va, n_classes,
                            v_pad=v_max)
            for c, d in enumerate(datasets)]


def build_stores(datasets, models, ccfg, families: tuple,
                 store_capacity: Optional[int], topology: str, seed: int):
    """The synchronous exchange over the topology (full graph = paper
    setup). Each reachable family is materialized with ONE batched
    multi-model forward per (family, client) — the exchange-layer hot
    path."""
    n, F = len(datasets), len(families)
    neighbors = make_topology(topology, n, seed=seed)
    stores = empty_stores(datasets, F, ccfg.n_classes, store_capacity)
    for c in range(n):
        reachable = sorted(set([c] + list(neighbors[c]))) \
            if topology != "full" else list(range(n))
        for fi, fam in enumerate(families):
            params_seq = [models[(o, fam)][0] for o in reachable]
            fam_preds = predict_probs_batched(fam, ccfg, params_seq,
                                              datasets[c].x_va)
            for o, pv in zip(reachable, fam_preds):
                stores[c].add(make_entry(o, fam, fi, models, ccfg, F),
                              preds=pv)
    return stores
