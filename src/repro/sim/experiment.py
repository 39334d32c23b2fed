"""`Experiment`: the one composable entry point for every FedPAE run.

`Experiment.from_spec(spec).run()` builds the world, stores, engine, and
p2p stack a declarative `ExperimentSpec` describes, dispatches to the
synchronous or asynchronous driver, and returns a structured `RunResult`
(test accuracy, val-acc curves, dissemination coverage, net counters,
spec echo) that sweep harnesses consume directly (DESIGN.md §9).

Callers that already hold collaborators (datasets, trained models,
transport/gossip/churn/repair objects, a train-cost model) inject them
through `Experiment(...)`'s keyword overrides: injected objects take the
place of registry-built ones, and everything else is built from the
spec. Both paths execute the same driver code, so an injected run and a
pure-spec run of the same scenario produce bit-identical traces (proven
in tests/test_spec.py).

`build()` without `run()` materializes datasets / models / stores /
engine for analysis scripts that drive selection themselves (e.g.
examples/pareto_front.py).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, List, Optional

import numpy as np

from repro.core.bench import BenchEntry
from repro.core.engine import SelectionEngine
from repro.fl.client import accuracy, predict_probs
from repro.fl.scheduler import AsyncConfig, AsyncTrace, simulate_async
from repro.obs.metrics import json_ready
from repro.obs.probes import attach_metrics, finalize_run, make_obs
from repro.sim.build import (_seeded, build_client_datasets, build_faults,
                             build_network, build_prediction_world,
                             build_serving, build_stores,
                             build_world_stores, empty_stores, make_entry,
                             train_all_clients)
from repro.sim.spec import ExperimentSpec

_IMAGE_KINDS = ("synthetic_images", "external")


@dataclasses.dataclass
class RunResult:
    """Structured outcome of one experiment — everything the examples,
    benchmarks, and sweep harnesses report, plus handles to the live
    objects (stores, engine, p2p stack) for post-hoc analysis."""
    spec: ExperimentSpec
    mode: str
    test_acc: Optional[np.ndarray] = None     # (N,) final-ensemble test acc
    local_frac: Optional[np.ndarray] = None   # sync: local-member fraction
    chromosomes: Optional[list] = None        # sync: per-client ensembles
    member_val_acc: Optional[list] = None     # sync: per-member val acc
    selections: Optional[dict] = None         # async: c -> [(t, val_acc)]
    select_batches: Optional[list] = None     # async: (t, batch_size)
    curve: Optional[list] = None              # async: (bytes_sent, mean acc)
    coverage: Optional[float] = None          # async: dissemination fraction
    t_full: Optional[float] = None            # async: time to coverage 1.0
    net: Optional[dict] = None                # transport/gossip/repair stats
    perf: Optional[dict] = None               # backend throughput counters
    trace: Optional[AsyncTrace] = None
    metrics: Optional[object] = None          # obs: collected MetricsFrame
    stores: Optional[list] = None
    engine: Optional[SelectionEngine] = None
    models: Optional[dict] = None
    transport: Optional[object] = None
    gossip: Optional[object] = None
    churn: Optional[object] = None
    repair: Optional[object] = None

    def summary(self) -> dict:
        """Compact JSON-able report (the `repro.sim.run` CLI output)."""
        d: dict = {"mode": self.mode, "seed": self.spec.seed,
                   "data_kind": self.spec.data.kind,
                   "n_clients": self.spec.data.n_clients}
        if self.test_acc is not None:
            d["test_acc_mean"] = round(float(np.mean(self.test_acc)), 4)
            d["test_acc"] = [round(float(a), 4) for a in self.test_acc]
        if self.local_frac is not None:
            d["local_frac_mean"] = round(float(np.mean(self.local_frac)), 4)
        if self.selections is not None:
            d["n_selections"] = int(sum(len(v)
                                        for v in self.selections.values()))
        if self.coverage is not None:
            d["coverage"] = round(float(self.coverage), 4)
            d["t_full"] = (None if self.t_full is None
                           or math.isnan(self.t_full)
                           else round(float(self.t_full), 3))
        if self.trace is not None:
            d["n_events"] = len(self.trace.events)
        if self.net is not None:
            d["net"] = self.net
        if self.perf is not None:
            d["perf"] = self.perf
        if self.metrics is not None:
            d["obs"] = {"n_scalars": len(self.metrics.scalars),
                        "n_series": len(self.metrics.series)}
        # strict-JSON guarantee: no bare NaN/Inf tokens ever reach a
        # dumped summary (json.dump(..., allow_nan=False) never raises)
        return json_ready(d)


class Experiment:
    """Builds and runs the scenario an `ExperimentSpec` describes.

    Keyword overrides inject pre-built collaborators: anything injected
    is used as-is, anything absent is built from the spec through the
    component registry.
    """

    def __init__(self, spec: ExperimentSpec, *, datasets=None,
                 models=None, ccfg=None, transport=None, gossip=None,
                 churn=None, repair=None,
                 train_cost: Optional[Callable] = None):
        self.spec = spec
        self.datasets = datasets
        self.models = models
        self.ccfg = ccfg
        self.world = None            # prediction_world: (labels, mats)
        self.stores: Optional[list] = None
        self.engine: Optional[SelectionEngine] = None
        self.neighbors = None
        self.transport = transport
        self.gossip = gossip
        self.churn = churn
        self.repair = repair
        self.train_cost = train_cost
        self.faults = None           # repro.faults.FaultController (or None)
        self.admission = None        # repro.faults.AdmissionController
        self.serving = None          # repro.serve.ServingEngine (or None)
        self.obs = None              # repro.obs.Obs once built (or None)
        self._sinks: list = []
        self._injected = {"transport": transport, "gossip": gossip,
                          "churn": churn, "repair": repair,
                          "train_cost": train_cost}
        self._built = False
        self._ran = False
        if datasets is not None and len(datasets) != spec.data.n_clients:
            raise ValueError(
                f"injected datasets ({len(datasets)} clients) do not match "
                f"spec.data.n_clients={spec.data.n_clients}")

    @classmethod
    def from_spec(cls, spec: ExperimentSpec) -> "Experiment":
        return cls(spec)

    # ---- properties ----------------------------------------------------
    @property
    def n_classes(self) -> int:
        return self.spec.data.n_classes

    @property
    def models_per_client(self) -> int:
        if self.spec.data.kind in _IMAGE_KINDS:
            return len(self.spec.train.families)
        return self.spec.data.models_per_client

    # ---- staged construction ------------------------------------------
    def _ensure_world(self) -> None:
        data = self.spec.data
        if data.kind == "synthetic_images" and self.datasets is None:
            self.datasets = build_client_datasets(data, self.spec.seed)
        elif data.kind == "external" and self.datasets is None:
            raise ValueError('data.kind="external" requires datasets to be '
                             "injected (Experiment(spec, datasets=...))")
        elif data.kind == "prediction_world" and self.world is None:
            self.world = build_prediction_world(data, self.spec.seed)

    def prepare_data(self):
        """Materialize (and return) just the world — datasets for image
        kinds, (labels, mats) for prediction worlds — without training
        or store construction. Lets benchmarks keep data generation
        outside their timed regions."""
        self._ensure_world()
        return self.datasets if self.spec.data.kind in _IMAGE_KINDS \
            else self.world

    def _ensure_models(self) -> None:
        """Local training (images worlds only)."""
        if self.spec.data.kind not in _IMAGE_KINDS or \
                self.models is not None:
            return
        self._ensure_world()
        self.models, self.ccfg = train_all_clients(
            self.datasets, self.spec.train, self.n_classes, self.spec.seed)

    def build(self) -> "Experiment":
        """Materialize everything the run needs: world, trained models,
        stores (filled for sync, empty for async), engine, and — async —
        the registry-built p2p stack. Idempotent."""
        if self._built:
            return self
        spec = self.spec
        data, sel = spec.data, spec.selection
        self._ensure_world()
        sync = spec.schedule.mode == "sync"
        self.obs = make_obs(spec.obs)
        if spec.obs.sinks and self.obs is None:
            raise ValueError(
                "obs.sinks declared but obs.enabled is false — a sink "
                "with nothing to write is a misconfigured run, not a "
                "default one")
        if self.obs is not None and self.obs.trace is not None and (
                sync or spec.schedule.backend.name != "event"):
            raise ValueError(
                "obs.trace=true requires schedule.mode='async' with "
                "schedule.backend='event': the Perfetto trace records "
                "per-event slices, which the "
                f"{'sync driver' if sync else 'compiled array world'} "
                "does not produce")
        if sync and spec.faults.enabled:
            raise ValueError(
                'schedule.mode="sync" cannot honor the faults section: '
                "fault injection (and validation-gated admission) drives "
                "the asynchronous event loop — switch to "
                'schedule.mode="async" or drop spec.faults')
        if sync and spec.serve.enabled:
            raise ValueError(
                'schedule.mode="sync" cannot honor the serve section: '
                "query traffic interleaves with the asynchronous event "
                'loop — switch to schedule.mode="async" or drop '
                "spec.serve")
        if sync and data.kind not in _IMAGE_KINDS:
            raise ValueError(
                f'schedule.mode="sync" needs image datasets '
                f'(data.kind in {_IMAGE_KINDS}), got {data.kind!r}')
        if sync and spec.schedule.backend.name != "event":
            raise ValueError(
                f'schedule.mode="sync" runs no simulation loop — '
                f"schedule.backend={spec.schedule.backend.name!r} only "
                'applies to schedule.mode="async"')
        if sync:
            declared = [s for s in ("transport", "gossip", "churn",
                                    "repair")
                        if getattr(spec.network, s) is not None]
            injected = [s for s, v in self._injected.items()
                        if v is not None]
            if declared or injected:
                what = (f"spec component(s) {declared}" if declared
                        else "") + (" and " if declared and injected
                                    else "") + \
                       (f"injected collaborator(s) {injected}"
                        if injected else "")
                raise ValueError(
                    f'schedule.mode="sync" cannot honor {what}: the '
                    "synchronous protocol has no exchange simulation — "
                    'switch to schedule.mode="async" or drop them '
                    "(silently ignoring them would report a lossless "
                    "run as if the declared network had been simulated)")
        if data.kind in _IMAGE_KINDS:
            self._ensure_models()
            families = spec.train.families
            self.stores = (
                build_stores(self.datasets, self.models, self.ccfg,
                             families, sel.store_capacity,
                             spec.network.topology, spec.seed) if sync
                else empty_stores(self.datasets, len(families),
                                  self.n_classes, sel.store_capacity))
        elif data.kind == "prediction_world":
            labels, _ = self.world
            self.stores = build_world_stores(data, labels,
                                             sel.store_capacity)
        if self.stores is not None and sel.enabled:
            self.engine = SelectionEngine(
                self.stores, sel.nsga(spec.seed),
                use_kernel=sel.use_kernel,
                seed=sel.seed if sel.seed is not None else spec.seed,
                ensemble_k=(sel.ensemble_k if sel.ensemble_k is not None
                            else sel.k),
                metrics=self.obs.metrics if self.obs is not None
                else None, shards=sel.shards)
        if not sync:
            n_val = (max(len(d.y_va) for d in self.datasets)
                     if self.datasets else None)
            # injected collaborators participate in the build context,
            # so spec-built dependents (repair around gossip, gossip
            # around churn) wire against the instances that actually run
            net = build_network(spec, data.n_clients, n_val=n_val,
                                injected=self._injected)
            self.neighbors = net["neighbors"]
            for slot in ("transport", "gossip", "churn", "repair",
                         "train_cost"):
                setattr(self, slot, net[slot])
            if spec.faults.injectors:
                self.faults = build_faults(spec, data.n_clients)
            if self.faults is not None \
                    and self.faults.byzantine is not None \
                    and self.stores is None:
                raise ValueError(
                    "the byzantine injector poisons prediction matrices, "
                    f"but data.kind={data.kind!r} builds no stores — "
                    "silently injecting nothing would report a clean run "
                    "as an attacked one")
            if spec.faults.admission is not None:
                if self.stores is None:
                    raise ValueError(
                        "the admission gate screens against local "
                        "validation labels, but data.kind="
                        f"{data.kind!r} builds no stores")
                from repro.faults import AdmissionController
                from repro.sim.registry import build as build_component
                fseed = (spec.faults.seed if spec.faults.seed is not None
                         else spec.seed)
                adm_cfg = build_component(
                    "admission", _seeded(spec.faults.admission, fseed),
                    {"n_clients": data.n_clients, "seed": fseed,
                     "spec": spec})
                self.admission = AdmissionController(adm_cfg, self.stores)
            if spec.serve.enabled:
                if self.stores is None:
                    raise ValueError(
                        "the serve section answers queries from "
                        f"prediction stores, but data.kind={data.kind!r} "
                        'builds none — use "prediction_world" or an '
                        "image world")
                if self.engine is None:
                    raise ValueError(
                        "the serve section needs selection.enabled=True: "
                        "queries are answered from selected ensembles "
                        "and the monitor triggers re-selection")
                if spec.serve.monitor and \
                        not spec.schedule.select_during_run:
                    raise ValueError(
                        "serve.monitor=True triggers re-selection "
                        "through the in-run select grid, but "
                        "schedule.select_during_run=False disables it — "
                        "enable in-run selection or set "
                        "serve.monitor=False")
                if data.kind not in _IMAGE_KINDS and any(
                        cs.name == "covariate_shift"
                        for cs in spec.serve.drift):
                    raise ValueError(
                        "drift[covariate_shift] transforms real query "
                        f"inputs, but data.kind={data.kind!r} has none "
                        "— use label_shift or an image world")
                pools = ([(d.x_te, d.y_te) for d in self.datasets]
                         if data.kind in _IMAGE_KINDS else None)
                self.serving = build_serving(spec, data.n_clients,
                                             self.stores, self.engine,
                                             query_pools=pools)
        if self.obs is not None:
            # repoint the instrumented subsystems' NULL_METRICS defaults
            # at the run's live registry
            attach_metrics(self.obs.metrics, self.transport, self.gossip,
                           self.repair, self.serving)
        if spec.obs.sinks:
            from repro.sim.registry import build as build_component
            ctx = {"obs": self.obs, "spec": spec,
                   "n_clients": data.n_clients}
            self._sinks = [build_component("sink", s, ctx)
                           for s in spec.obs.sinks]
        self._built = True
        return self

    # ---- drivers -------------------------------------------------------
    def run(self) -> RunResult:
        """Single-shot: stores, gossip version vectors, and transport
        counters are mutated by the drive, so a second run() over the
        same state would be a silently-different experiment — construct
        a fresh Experiment (or `Experiment.from_spec(result.spec)`) to
        re-run."""
        if self._ran:
            raise RuntimeError(
                "this Experiment already ran; its stores and p2p state "
                "are consumed — build a fresh one with "
                "Experiment.from_spec(spec) to re-run")
        self.build()
        self._ran = True
        res = (self._run_sync() if self.spec.schedule.mode == "sync"
               else self._run_async())
        if self.obs is not None:
            finalize_run(self.obs, res)
        for sink in self._sinks:
            sink(res)
        return res

    def _run_sync(self) -> RunResult:
        """The paper's synchronous protocol: stores complete, ONE batched
        selection over every client, then masked lazy serving."""
        engine, stores = self.engine, self.stores
        if engine is None:
            raise ValueError('schedule.mode="sync" requires '
                             "selection.enabled=True")
        engine.select()  # one vmapped NSGA-II run for ALL clients
        accs, local_fracs, chroms, member_accs = [], [], [], []
        for c, data in enumerate(self.datasets):
            vote, chrom = engine.serve(c, data.x_te)
            mask = chrom > 0.5
            accs.append(accuracy(vote, data.y_te))
            local_fracs.append(float((mask & stores[c].is_local()).sum()
                                     / max(1, mask.sum())))
            chroms.append(chrom)
            res = engine.results.get(c)  # absent when the store can't fill
            member_accs.append(np.asarray(res["member_acc"])
                               if res is not None
                               else np.full(stores[c].capacity, np.nan))
        return RunResult(
            spec=self.spec, mode="sync", test_acc=np.array(accs),
            local_frac=np.array(local_fracs), chromosomes=chroms,
            member_val_acc=member_accs, stores=stores, engine=engine,
            models=self.models)

    def _run_async(self) -> RunResult:
        """Dispatch to the simulator backend the spec names —
        registry-resolved like every other component, so
        `schedule.backend` flips between the event-granular golden
        reference and the compiled array world without touching any
        caller."""
        from repro.sim.registry import build as build_component
        runner = build_component("backend", self.spec.schedule.backend,
                                 {"spec": self.spec, "seed": self.spec.seed,
                                  "n_clients": self.spec.data.n_clients})
        return runner(self)

    def _run_async_event(self) -> RunResult:
        """The event-granular asynchronous driver (the golden
        reference): virtual-clock simulation where arrivals
        incrementally materialize the stores and debounced select
        events run REAL batched re-selection through the shared engine,
        over whatever p2p stack the spec declares."""
        spec = self.spec
        data, sched = spec.data, spec.schedule
        n, mpc = data.n_clients, self.models_per_client
        stores, engine = self.stores, self.engine
        acfg = AsyncConfig(
            n_clients=n, models_per_client=mpc,
            speed_lognorm_sigma=sched.speed_lognorm_sigma,
            link_latency=sched.link_latency,
            select_debounce=sched.select_debounce,
            seed=sched.seed if sched.seed is not None else spec.seed)

        on_add = None
        faults, adm = self.faults, self.admission
        chaos = faults is not None or adm is not None
        base_entry = None
        if data.kind in _IMAGE_KINDS:
            families = spec.train.families
            models, ccfg, F = self.models, self.ccfg, len(families)

            if not chaos:
                def on_add(c, model_key, t):
                    owner, m = model_key
                    stores[c].add(make_entry(owner, families[m], m,
                                             models, ccfg, F), t=t)
            else:
                def base_entry(c, model_key):
                    owner, m = model_key
                    entry = make_entry(owner, families[m], m, models,
                                       ccfg, F)
                    return entry, entry.predict(stores[c].x_val)
        elif data.kind == "prediction_world":
            _, mats = self.world
            C = data.n_classes

            if not chaos:
                def on_add(c, model_key, t):
                    owner, m = model_key
                    gid = owner * mpc + m
                    stores[c].add(
                        BenchEntry(model_id=gid, owner=owner,
                                   family=f"f{m}",
                                   predict=lambda x: np.full(
                                       (len(x), C), 1.0 / C, np.float32)),
                        preds=mats[(c, gid)], t=t)
            else:
                def base_entry(c, model_key):
                    owner, m = model_key
                    gid = owner * mpc + m
                    entry = BenchEntry(
                        model_id=gid, owner=owner, family=f"f{m}",
                        predict=lambda x: np.full((len(x), C), 1.0 / C,
                                                  np.float32))
                    return entry, mats[(c, gid)]
        if chaos and base_entry is not None:
            # the fault-aware gossip -> store path: poison byzantine
            # payloads (and their test-time forwards), decode
            # corrupt-admitted deliveries as garbage, screen remote
            # arrivals through the validation gate
            def on_add(c, model_key, t):
                entry, preds = base_entry(c, model_key)
                owner, gid = entry.owner, entry.model_id
                if faults is not None and owner != c:
                    if faults.is_byzantine(owner):
                        preds = faults.poison_payload(preds, c, gid)
                        # serving this entry must yield the poisoned
                        # outputs too: wrap the forward and strip the raw
                        # params so the batched family path — which would
                        # serve TRUE outputs — never picks it up
                        entry = dataclasses.replace(
                            entry, params=None, ccfg=None,
                            predict=lambda x, f=entry.predict, cc=c,
                            g=gid: faults.poison_matrix(f(x), cc, g))
                    if faults.take_corrupt(c, model_key):
                        preds = faults.corrupt_matrix(preds, c, gid)
                if adm is not None and owner != c:
                    if adm.screen(c, gid, preds, stores[c]) != "admitted":
                        return
                stores[c].add(entry, preds=preds, t=t)

        on_crash_cb = None
        if faults is not None:
            def on_crash_cb(c, t):
                # the bench wipe happened in the scheduler; mirror it in
                # the volatile driver state (store slots, quarantine pen)
                if stores is not None:
                    stores[c].wipe()
                if adm is not None:
                    adm.on_crash(c)

        curve: List[tuple] = []
        latest: Dict[int, float] = {}
        on_select_batch = None
        if engine is not None and sched.select_during_run:
            def on_select_batch(clients, bench_ids, t):
                fresh = engine.select(clients, t=t)
                out = {c: float(r["val_accuracy"])
                       for c, r in fresh.items()}
                latest.update(out)
                if self.transport is not None and latest:
                    curve.append((self.transport.stats.bytes_sent,
                                  float(np.mean(list(latest.values())))))
                return out

        trace = simulate_async(
            acfg, self.neighbors, train_cost=self.train_cost,
            on_add=on_add, on_select_batch=on_select_batch,
            transport=self.transport, gossip=self.gossip,
            churn=self.churn, repair=self.repair, faults=faults,
            on_crash=on_crash_cb, serving=self.serving, obs=self.obs)
        if adm is not None:
            trace.net = dict(trace.net or {})
            trace.net["admission"] = adm.as_dict()
        if self.serving is not None:
            trace.net = dict(trace.net or {})
            trace.net["serve"] = self.serving.stats_dict()

        finals = [s[-1][1] if s else 0
                  for s in trace.bench_sizes.values()]
        coverage = sum(finals) / (n * n * mpc)
        t_full = (max(s[-1][0] for s in trace.bench_sizes.values())
                  if coverage == 1.0 else float("nan"))
        test_acc = None
        if data.kind in _IMAGE_KINDS and engine is not None:
            test_acc = np.array([accuracy(engine.serve(c, d.x_te)[0],
                                          d.y_te)
                                 for c, d in enumerate(self.datasets)])
        return RunResult(
            spec=spec, mode="async", test_acc=test_acc,
            selections=trace.selections,
            select_batches=trace.select_batches, curve=curve or None,
            coverage=coverage, t_full=t_full, net=trace.net,
            perf=trace.perf, trace=trace,
            stores=stores, engine=engine, models=self.models,
            transport=self.transport, gossip=self.gossip,
            churn=self.churn, repair=self.repair)

    # ---- baselines -----------------------------------------------------
    def local_ensemble(self) -> np.ndarray:
        """The paper's 'local' baseline on this experiment's world and
        models: each client mean-prob votes over only its own locally
        trained models. Trains (or reuses) the same models as `run()`."""
        self._ensure_models()
        accs = []
        for c, data in enumerate(self.datasets):
            probs = np.stack([predict_probs(f, self.ccfg,
                                            self.models[(c, f)][0],
                                            data.x_te)
                              for f in self.spec.train.families])
            accs.append(accuracy(probs.mean(0), data.y_te))
        return np.array(accs)
