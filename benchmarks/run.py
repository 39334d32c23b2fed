"""Benchmark suite entry point: one function per paper table (+ kernel and
roofline reports). Prints ``name,us_per_call,derived`` CSV rows.

Full-scale variants live in benchmarks/table{1..4}_*.py; this runner uses
reduced sizes so the whole suite finishes on one CPU core.
"""
from __future__ import annotations

import gc

import numpy as np

from repro.obs.metrics import Stopwatch


def bench_table1_accuracy():
    """Table I (reduced): FedPAE vs local vs FedAvg vs one pFL baseline.
    The FedPAE run is one declarative spec (repro.sim); the FL baselines
    reuse its datasets."""
    from benchmarks.common import row
    from repro.fl.baselines import BASELINES, FLConfig
    from repro.sim import (DataSpec, Experiment, ExperimentSpec,
                           ScheduleSpec, SelectionSpec, TrainSpec)

    spec = ExperimentSpec(
        data=DataSpec(kind="synthetic_images", n_clients=4, n_classes=8,
                      n_samples=2400, alpha=0.1),
        train=TrainSpec(families=("cnn4", "vgg", "resnet"),
                        max_epochs=10, patience=4, width=12),
        selection=SelectionSpec(pop_size=32, generations=20, k=3,
                                ensemble_k=3),
        schedule=ScheduleSpec(mode="sync"), seed=0)
    exp = Experiment.from_spec(spec)
    fl = FLConfig(rounds=40, local_steps=2,
                  families=spec.train.families, width=12)
    exp.prepare_data()  # data generation stays OUTSIDE the timed region
    sw = Stopwatch().start()
    local_acc = exp.local_ensemble()
    res = exp.run()
    t_fedpae = sw.stop() * 1e6
    accs = {"local": local_acc.mean(), "fedpae": res.test_acc.mean()}
    for m in ("fedavg", "lg_fedavg"):
        accs[m] = BASELINES[m](exp.datasets, 8, fl).mean()
    row("table1_accuracy", t_fedpae,
        " ".join(f"{k}={v:.3f}" for k, v in accs.items()))
    return local_acc, res


def bench_table2_negative_transfer(local_acc, res):
    """Table II (reduced): relative change range vs the local ensemble."""
    from benchmarks.common import row
    rel = (res.test_acc - local_acc) / np.maximum(local_acc, 1e-9)
    row("table2_negative_transfer", 0.0,
        f"fedpae_rel_range=({rel.min():+.1%};{rel.max():+.1%}) "
        f"local_frac={res.local_frac.mean():.2f}")


def bench_table3_scalability():
    """Table III (reduced): doubled client count, same total data."""
    from benchmarks.common import row
    from repro.sim import (DataSpec, Experiment, ExperimentSpec,
                           ScheduleSpec, SelectionSpec, TrainSpec)
    spec = ExperimentSpec(
        data=DataSpec(kind="synthetic_images", n_clients=8, n_classes=8,
                      n_samples=2400, alpha=0.1),
        train=TrainSpec(families=("cnn4", "vgg"), max_epochs=8,
                        patience=3, width=12),
        selection=SelectionSpec(pop_size=32, generations=15, k=3,
                                ensemble_k=3),
        schedule=ScheduleSpec(mode="sync"), seed=0)
    exp = Experiment.from_spec(spec)
    exp.prepare_data()  # data generation stays OUTSIDE the timed region
    sw = Stopwatch().start()
    local_acc = exp.local_ensemble()
    res = exp.run()
    row("table3_scalability", sw.stop() * 1e6,
        f"clients=8 local={local_acc.mean():.3f} fedpae={res.test_acc.mean():.3f}")


def bench_table4_cost():
    """Table IV: analytic FLOPs comparison (full-scale config)."""
    from benchmarks.common import row
    from benchmarks.table4_cost import family_forward_flops
    from repro.configs.paper_cnn import config as paper_config
    from repro.models.cnn import CNNConfig
    pc = paper_config(True)
    tr, sel = pc["train"], pc["selection"]
    ccfg = CNNConfig(n_classes=10, width=tr.width)
    f_avg = np.mean([family_forward_flops(f, ccfg) for f in tr.families])
    N, M, T, D, V = 20, 5, tr.max_epochs, 2100, 450
    P, G = sel.pop_size, sel.generations
    f_fit = 2 * (N * M) ** 2 + 2 * N * M
    fedpae = N * (M * 3 * f_avg * T * D + P * G * f_fit + 10 * V * f_avg)
    rounds = N * 500 * 1 * 10 * 3 * f_avg
    row("table4_cost", 0.0,
        f"fedpae_gflops={fedpae/1e9:.1f} fedavg_gflops={rounds/1e9:.1f} "
        f"ratio={rounds/max(fedpae,1):.2f}")


def bench_selection_throughput():
    """Serial per-client loop vs ONE vmapped NSGA-II run over all clients
    (the batched-engine tentpole). Same per-client PRNG streams, so both
    paths produce identical chromosomes — only wall-time differs."""
    import jax
    import jax.numpy as jnp
    from benchmarks.common import row, timed
    from repro.core.nsga2 import NSGAConfig, client_keys
    from repro.core.selection import select_ensemble, select_ensembles

    M, V, C = 16, 128, 8
    cfg = NSGAConfig(pop_size=32, generations=10, k=4, seed=0)
    rng = np.random.default_rng(0)
    for n_clients in (8, 16, 32):
        probs = jnp.asarray(rng.dirichlet(np.ones(C), size=(n_clients, M, V))
                            .astype(np.float32))
        labels = jnp.asarray(rng.integers(0, C, (n_clients, V)))
        keys = client_keys(cfg.seed, np.arange(n_clients))

        def serial():
            outs = [select_ensemble(probs[c], labels[c], cfg, key=keys[c])
                    for c in range(n_clients)]
            jax.block_until_ready(outs[-1]["chromosome"])
            return outs

        def batched():
            out = select_ensembles(probs, labels, cfg, keys=keys)
            jax.block_until_ready(out["chromosome"])
            return out

        outs, dt_serial = timed(serial, repeat=2)
        out, dt_batched = timed(batched, repeat=2)
        agree = all(np.array_equal(np.asarray(outs[c]["chromosome"]),
                                   np.asarray(out["chromosome"][c]))
                    for c in range(n_clients))
        row(f"selection_vmapped_N{n_clients}", dt_batched * 1e6,
            f"serial_us={dt_serial*1e6:.0f} "
            f"speedup={dt_serial/max(dt_batched,1e-12):.2f}x "
            f"chromosomes_match={agree}")


def bench_nsga2_microbench():
    """NSGA-II generation throughput (the paper's P x G hot loop)."""
    import jax
    import jax.numpy as jnp
    from benchmarks.common import row, timed
    from repro.core.nsga2 import NSGAConfig, run_nsga2
    from repro.core.objectives import population_objectives
    M = 100
    key = jax.random.PRNGKey(0)
    acc = jax.random.uniform(key, (M,))
    S = jax.random.uniform(key, (M, M))

    def eval_fn(pop):
        s, d = population_objectives(pop, acc, S)
        return jnp.stack([s, d], axis=1)

    cfg = NSGAConfig(pop_size=100, generations=100, k=5)

    def run():
        out = run_nsga2(eval_fn, M, cfg)
        jax.block_until_ready(out["pop"])
        return out

    _, dt = timed(run, repeat=2)
    row("nsga2_100x100", dt * 1e6, f"us_per_generation={dt*1e6/100:.0f}")


def bench_ensemble_fitness_kernel():
    """Pallas kernel (interpret) vs pure-jnp objectives."""
    import jax
    import jax.numpy as jnp
    from benchmarks.common import row, timed
    from repro.kernels.ensemble_fitness.kernel import ensemble_fitness
    from repro.kernels.ensemble_fitness.ref import ensemble_fitness_ref
    P, M = 256, 128
    key = jax.random.PRNGKey(0)
    pop = (jax.random.uniform(key, (P, M)) < 0.3).astype(jnp.float32)
    acc = jax.random.uniform(key, (M,))
    S = jax.random.uniform(key, (M, M))
    jref = jax.jit(ensemble_fitness_ref)
    _, dt_ref = timed(lambda: jax.block_until_ready(jref(pop, acc, S)))
    _, dt_ker = timed(lambda: jax.block_until_ready(
        ensemble_fitness(pop, acc, S, interpret=True)))
    row("ensemble_fitness_jnp", dt_ref * 1e6, f"P={P} M={M}")
    row("ensemble_fitness_pallas_interpret", dt_ker * 1e6,
        "CPU interpret mode; compiled path is TPU-only")


def bench_gossip_scale():
    """Gossip transport at 16/64/128 clients: bytes on the wire
    (prediction-matrix vs checkpoint exchange), streaming-store eviction
    counts at capacity 16, message-loss counters, and the one-shot
    batched selection latency over the full fleet. Each fleet size is
    one declarative spec (`select_during_run=False`: arrivals fill the
    bounded stores, selection is timed separately below)."""
    import jax
    import jax.numpy as jnp
    from benchmarks.common import row, timed
    from repro.core.bench import stack_stores
    from repro.core.nsga2 import NSGAConfig, client_keys
    from repro.core.selection import select_ensembles
    from repro.p2p import checkpoint_bytes
    from repro.sim import (ComponentSpec, DataSpec, Experiment,
                           ExperimentSpec, NetworkSpec, ScheduleSpec,
                           SelectionSpec)

    V, C, MPC, CAP = 128, 8, 2, 16
    n_params = 250_000  # checkpoint-exchange baseline (width-16 CNN scale)
    cfg = NSGAConfig(pop_size=32, generations=10, k=5, seed=0)
    for n in (16, 64, 128):
        spec = ExperimentSpec(
            data=DataSpec(kind="prediction_world", n_clients=n,
                          n_classes=C, n_val=V, models_per_client=MPC,
                          seed=n),
            # no engine: the sim only fills the bounded stores, and the
            # one-shot selection below is timed separately (the legacy
            # benchmark built no engine either)
            selection=SelectionSpec(enabled=False, store_capacity=CAP),
            network=NetworkSpec(
                topology="small_world", topology_k=4,
                transport=ComponentSpec("gossip", {
                    "base_latency": 0.05, "drop_prob": 0.1,
                    "bandwidth": 50e6, "inbox_capacity": 64}),
                gossip="push",
                churn=ComponentSpec("lognormal", {
                    "availability_beta": 0.1, "leave_prob": 0.05})),
            schedule=ScheduleSpec(
                mode="async", select_debounce=0.5,
                train_cost=ComponentSpec("affine",
                                         {"base": 1.0, "slope": 0.2})),
            seed=0)
        exp = Experiment.from_spec(spec)
        exp.build()  # world + stores + p2p stack outside the timer —
        sw = Stopwatch().start()  # the row times the simulation itself
        res = exp.run()
        dt_sim = sw.stop()
        evictions = sum(s.evictions for s in res.stores)
        tstats = res.net["transport"]
        pred_bytes = tstats["bytes_sent"]
        msgs = tstats["n_sent"]
        ckpt_bytes = msgs * checkpoint_bytes(n_params)
        row(f"gossip_sim_N{n}", dt_sim * 1e6,
            f"msgs={msgs} pred_MB={pred_bytes/1e6:.1f} "
            f"ckpt_MB={ckpt_bytes/1e6:.0f} "
            f"ratio={ckpt_bytes/max(pred_bytes,1):.0f}x "
            f"evictions={evictions} "
            f"dropped={tstats['n_dropped_link']}")

        # one-shot batched selection latency over the whole fleet
        preds, labels, masks = stack_stores(res.stores)
        keys = client_keys(cfg.seed, np.arange(n))
        jp, jl, jm = (jnp.asarray(preds), jnp.asarray(labels),
                      jnp.asarray(masks))
        _, dt_sel = timed(lambda: jax.block_until_ready(select_ensembles(
            jp, jl, cfg, keys=keys, model_mask=jm)["chromosome"]),
            repeat=2)
        row(f"gossip_select_N{n}", dt_sel * 1e6,
            f"capacity={CAP} us_per_client={dt_sel*1e6/n:.0f}")


def bench_lossy_repair():
    """Anti-entropy repair (DESIGN.md §8) at 16/64 clients on a lossy
    ring: dissemination coverage with vs without the digest/re-send
    loop, repair counters, and the byte overhead repair costs — the
    simulator wall time is the row's primary number. Pure-dissemination
    specs (`data.kind="none"`); repair on/off is one component slot."""
    from benchmarks.common import row
    from repro.sim import (ComponentSpec, DataSpec, Experiment,
                           ExperimentSpec, NetworkSpec, ScheduleSpec,
                           SelectionSpec)

    V, C, MPC, DROP = 128, 8, 2, 0.1
    for n in (16, 64):
        covs, nets, dt = {}, {}, {}
        for with_repair in (False, True):
            spec = ExperimentSpec(
                data=DataSpec(kind="none", n_clients=n, n_classes=C,
                              n_val=V, models_per_client=MPC),
                selection=SelectionSpec(enabled=False),
                network=NetworkSpec(
                    topology="ring",
                    transport=ComponentSpec("gossip", {
                        "base_latency": 0.05, "drop_prob": DROP,
                        "bandwidth": 50e6, "inbox_capacity": 64}),
                    gossip="push",
                    repair=(ComponentSpec("anti_entropy",
                                          {"max_rounds": 60,
                                           "max_attempts": 8})
                            if with_repair else None)),
                schedule=ScheduleSpec(
                    mode="async",
                    train_cost=ComponentSpec(
                        "affine", {"base": 1.0, "slope": 0.2})),
                seed=0)
            sw = Stopwatch().start()
            res = Experiment.from_spec(spec).run()
            dt[with_repair] = sw.stop()
            covs[with_repair] = res.coverage
            nets[with_repair] = res.net
        rs = nets[True]["repair"]
        byte_x = (nets[True]["transport"]["bytes_sent"]
                  / max(nets[False]["transport"]["bytes_sent"], 1))
        row(f"lossy_repair_N{n}", dt[True] * 1e6,
            f"coverage={covs[True]:.4f} norepair_coverage="
            f"{covs[False]:.4f} digests={rs['n_digests_sent']} "
            f"gaps={rs['n_gaps_found']} resends={rs['n_resends']} "
            f"byte_overhead={byte_x:.2f}x "
            f"norepair_us={dt[False]*1e6:.0f}")


def bench_faults(smoke: bool = False):
    """Fault subsystem (DESIGN.md §12) on pure-dissemination worlds: the
    scheduler-level injectors at benchmark speed (no training, no
    stores). Three rows, each one declarative spec on a 16-client lossy
    ring with anti-entropy repair:

      crash     — 25% of clients crash (volatile state lost) and rejoin;
                  re-dissemination under a fresh gossip incarnation must
                  still reach FULL coverage;
      partition — the ring is bisected for a window; after the heal
                  event re-arms quiesced repair streams, coverage must
                  reach 1.0 (and t_full necessarily falls after heal);
      corrupt   — 15% per-delivery corruption, 80% checksum coverage:
                  detected payloads are discarded + re-sent (coverage
                  still 1.0), admitted-corrupt ones are counted.

    Every row's primary number is the simulation wall time — the fault
    paths ride the same event loop, so this doubles as a perf canary for
    the `faults is not None` branches."""
    from benchmarks.common import row
    from repro.sim import Experiment, ExperimentSpec

    def fault_spec(faults: dict, drop: float = 0.1) -> ExperimentSpec:
        return ExperimentSpec.from_dict({
            "data": {"kind": "none", "n_clients": 16, "n_classes": 8,
                     "n_val": 128, "models_per_client": 2},
            "selection": {"enabled": False},
            "network": {"topology": "ring",
                        "transport": {"name": "gossip",
                                      "params": {"base_latency": 0.05,
                                                 "jitter": 1.0,
                                                 "bandwidth": 50e6,
                                                 "drop_prob": drop,
                                                 "inbox_capacity": 64}},
                        "gossip": "push",
                        "repair": {"name": "anti_entropy",
                                   "params": {"max_rounds": 60,
                                              "max_attempts": 8}}},
            "schedule": {"mode": "async",
                         "train_cost": {"name": "affine",
                                        "params": {"base": 1.0,
                                                   "slope": 0.2}}},
            "faults": faults, "seed": 0})

    def run(name, faults, derive):
        spec = fault_spec(faults)
        exp = Experiment.from_spec(spec)
        exp.build()
        sw = Stopwatch().start()
        res = exp.run()
        dt = sw.stop()
        row(name, dt * 1e6, derive(res))

    run("faults_crash_N16",
        {"injectors": [{"name": "crash_restart",
                        "params": {"fraction": 0.25, "at": 1.5,
                                   "downtime": 1.5}}]},
        lambda r: f"coverage={r.coverage:.4f} "
                  f"crashes={r.net['faults']['n_crashes']} "
                  f"restarts={r.net['faults']['n_restarts']}")
    run("faults_partition_N16",
        {"injectors": [{"name": "partition",
                        "params": {"mode": "halves", "start": 1.0,
                                   "duration": 3.0}}]},
        lambda r: f"coverage={r.coverage:.4f} t_full={r.t_full:.2f} "
                  f"heal_t=4.00 "
                  f"blocked={r.net['faults']['n_partition_blocked']}")
    run("faults_corrupt_N16",
        {"injectors": [{"name": "corruption",
                        "params": {"flip_prob": 0.15,
                                   "detect_prob": 0.8}}]},
        lambda r: f"coverage={r.coverage:.4f} "
                  f"detected={r.net['transport']['n_corrupt_detected']} "
                  f"admitted={r.net['transport']['n_corrupt_admitted']}")


def bench_serve(smoke: bool = False):
    """Online serving subsystem (DESIGN.md §14) on prediction worlds:
    Poisson query traffic + a scheduled label shift + the accuracy
    monitor, at two fleet sizes. Each row's primary number is the
    simulation wall time (the query/drift events ride the same loop —
    a perf canary for the `serving is not None` branches); derived
    carries the serving telemetry: queries answered, virtual-time
    p50/p99 query latency, monitor re-selections, and the
    stale-ensemble regret captured by re-selecting."""
    from benchmarks.common import row
    from repro.sim import Experiment, ExperimentSpec

    def serve_spec(n: int) -> ExperimentSpec:
        return ExperimentSpec.from_dict({
            "data": {"kind": "prediction_world", "n_clients": n,
                     "n_classes": 8, "n_val": 64, "models_per_client": 2,
                     "quality_local": [0.3, 0.5],
                     "quality_remote": [0.25, 0.55]},
            "selection": {"enabled": True, "pop_size": 16,
                          "generations": 4, "k": 3},
            "network": {"topology": "ring",
                        "transport": {"name": "gossip",
                                      "params": {"base_latency": 0.05,
                                                 "jitter": 1.0,
                                                 "bandwidth": 50e6,
                                                 "drop_prob": 0.05,
                                                 "inbox_capacity": 64}},
                        "gossip": "push",
                        "repair": {"name": "anti_entropy",
                                   "params": {"max_rounds": 60,
                                              "max_attempts": 8}}},
            "schedule": {"mode": "async",
                         "train_cost": {"name": "affine",
                                        "params": {"base": 1.0,
                                                   "slope": 0.2}}},
            "serve": {"traffic": {"name": "poisson",
                                  "params": {"rate": 20.0, "batch": 8,
                                             "start": 2.0,
                                             "duration": 8.0}},
                      "drift": [{"name": "label_shift",
                                 "params": {"at": 7.0, "classes": [7],
                                            "skew": 1.0}}],
                      "monitor": True, "window": 64,
                      "threshold": 0.15, "debounce": 1.0},
            "seed": 0})

    for n in ((16,) if smoke else (16, 64)):
        exp = Experiment.from_spec(serve_spec(n))
        exp.build()
        sw = Stopwatch().start()
        res = exp.run()
        dt = sw.stop()
        sv = res.net["serve"]
        row(f"serve_drift_N{n}", dt * 1e6,
            f"queries={sv['n_queries']} "
            f"lat_p50={sv['latency_p50']:.5f} "
            f"lat_p99={sv['latency_p99']:.5f} "
            f"resel={sv['n_reselections']} regret={sv['regret']:.3f}")


def bench_select_incremental(smoke: bool = False):
    """Restack vs device-resident incremental select (DESIGN.md §7): the
    same fleet, the same NSGA-II, the same per-client streams — one
    engine re-stacks + re-derives acc/S from the raw (N, M, V, C) tensors
    on every select, the other scatters only the rows dirtied since the
    last select and launches the GA on cached statistics.

    Each row's primary number is the per-select STATE-UPDATE wall time —
    the stage the tentpole replaces: host restack + device transfer +
    full-stats rebuild (restack path) vs dirty-row flush (incremental
    path). The shared GA stage and the end-to-end select times ride in
    `derived` (select_us / restack_select_us), since NSGA-II itself is
    identical work on both paths. Client-count sweep at 10% dirty per
    select, plus a dirty-fraction sweep at N=64."""
    import jax
    import jax.numpy as jnp
    from benchmarks.common import row
    from repro.core.bench import BenchEntry, PredictionStore, stack_stores
    from repro.core.engine import SelectionEngine
    from repro.core.nsga2 import NSGAConfig
    from repro.core.selection import selection_stats

    # a 128-model fleet bench (64 owners x 2 families) on every client —
    # the regime the async gossip sim reaches, where the O(N·M²·V·C)
    # restack stats rebuild is the per-select bottleneck
    V, C, CAP = 128, 16, 128
    cfg = NSGAConfig(pop_size=8, generations=2, k=5, seed=0)

    def _pred(rng):
        p = rng.random((V, C)).astype(np.float32)
        return p / p.sum(1, keepdims=True)

    def _add(stores, rng, c, gid):
        stores[c].add(BenchEntry(
            model_id=gid, owner=gid % len(stores), family="f",
            predict=lambda x: np.zeros((len(x), C), np.float32)),
            preds=_pred(rng))

    def touch(stores, rng, frac):
        """Dirty `frac` of the fleet's MODEL SLOTS: re-materialize that
        many models at every store — the async gossip pattern, where an
        updated model's prediction matrix reaches each client's
        slot-aligned store within the debounce window."""
        for gid in rng.choice(CAP, max(1, int(frac * CAP)), replace=False):
            for c in range(len(stores)):
                _add(stores, rng, c, int(gid))

    def restack_state(stores, v_max):
        """What the restack path must do before the GA can launch."""
        preds, labels, _ = stack_stores(stores, v_to=v_max)
        acc, S = selection_stats(jnp.asarray(preds), jnp.asarray(labels))
        jax.block_until_ready(S)

    def run_pair(n, frac, reps=3):
        rng = np.random.default_rng(n)
        stores = [PredictionStore(c, CAP, np.zeros((V, 2), np.float32),
                                  rng.integers(0, C, V), C)
                  for c in range(n)]
        for c in range(n):
            for gid in range(CAP):
                _add(stores, rng, c, gid)
        eng_inc = SelectionEngine(stores, cfg, ensemble_k=cfg.k)
        eng_re = SelectionEngine(stores, cfg, ensemble_k=cfg.k,
                                 device_resident=False)
        dev = eng_inc.device
        for _ in range(3):  # compile both paths + the flush variants
            touch(stores, rng, frac)
            restack_state(stores, dev.v_max)
            eng_inc.select()
            eng_re.select()
        st_inc, st_re, tot_inc, tot_re = [], [], [], []
        for _ in range(reps):
            touch(stores, rng, frac)
            sw = Stopwatch()
            sw.start()                         # incremental state update
            dev.flush()
            jax.block_until_ready(dev.S)
            d_flush = sw.stop()
            sw.start()                         # + GA on cached stats
            eng_inc.select()
            d_select = sw.stop()
            sw.start()                         # restack state update
            restack_state(stores, dev.v_max)
            d_restack = sw.stop()
            sw.start()                         # full restack select
            eng_re.select()
            d_reselect = sw.stop()
            st_inc.append(d_flush)
            tot_inc.append(d_flush + d_select)
            st_re.append(d_restack)
            tot_re.append(d_reselect)
        agree = all(np.array_equal(eng_inc.results[c]["chromosome"],
                                   eng_re.results[c]["chromosome"])
                    for c in range(n))
        med = lambda xs: float(np.median(xs))  # noqa: E731
        return (med(st_inc), med(st_re), med(tot_inc), med(tot_re), agree)

    def emit(name, stats, extra=""):
        st_inc, st_re, tot_inc, tot_re, agree = stats
        row(name, st_inc * 1e6,
            f"restack_state_us={st_re*1e6:.0f} "
            f"state_speedup={st_re/max(st_inc,1e-12):.2f}x "
            f"select_us={tot_inc*1e6:.0f} "
            f"restack_select_us={tot_re*1e6:.0f} "
            f"select_speedup={tot_re/max(tot_inc,1e-12):.2f}x "
            f"{extra}match={agree}")

    # --smoke (CI) trims the heaviest work: the N=128 row and one timing
    # rep — the perf gate only consumes the N=64 rows
    reps = 2 if smoke else 3
    for n in (16, 64) if smoke else (16, 64, 128):
        stats = run_pair(n, 0.1, reps=reps)
        emit(f"select_incremental_N{n}", stats, "dirty_frac=0.10 ")
        if n == 64:  # the 10% point doubles as the sweep's middle row
            emit("select_incremental_dirty10", stats, "N=64 ")
    for frac, tag in ((0.01, "dirty1"), (1.0, "dirty100")):
        emit(f"select_incremental_{tag}", run_pair(64, frac, reps=reps),
             f"N=64 dirty_frac={frac} ")


def bench_simloop(smoke: bool = False):
    """Event loop vs the compiled array world (DESIGN.md §10) on the
    same deterministic dissemination scenario: small-world push gossip,
    constant hop latency, no drops — the tier where the two backends
    must agree EXACTLY on every net counter. Each compiled row carries
    its speedup over the event run at the same fleet size; the full
    (non-smoke) variant adds a compiled-only N=10000 row with a coarser
    tick — the regime the backend exists for, where the event loop
    would take tens of minutes."""
    from benchmarks.common import row
    from repro.sim import Experiment, ExperimentSpec

    def simloop_spec(n, backend, params, k):
        return ExperimentSpec.from_dict({
            "data": {"kind": "none", "n_clients": n,
                     "models_per_client": 1},
            "selection": {"enabled": False},
            "network": {"topology": "small_world", "topology_k": k,
                        "transport": {"name": "gossip",
                                      "params": {"base_latency": 0.05,
                                                 "jitter": 0.0,
                                                 "drop_prob": 0.0}},
                        "gossip": "push"},
            "schedule": {"mode": "async", "select_during_run": False,
                         "backend": {"name": backend, "params": params}},
            "seed": 0})

    def timed(spec, keep=()):
        """One hermetic timed run: build, collect, run, then keep only
        the requested scalar fields so a finished run's multi-million-
        entry trace never stays live while a later run is timed (cyclic
        GC scans every live object — retained results skewed paired
        timings by >10%)."""
        exp = Experiment.from_spec(spec)
        exp.build()
        gc.collect()
        sw = Stopwatch().start()
        r = exp.run()
        dt = sw.stop()
        out = {k: fn(r) for k, fn in keep}
        del r, exp
        return dt, out

    scalar_keep = (
        ("coverage", lambda r: r.coverage),
        ("t_full", lambda r: r.t_full),
        ("msgs", lambda r: r.net["transport"]["n_sent"]),
    )
    for n in (128, 1024):
        dt_ev, ev = timed(simloop_spec(n, "event", {}, 4), scalar_keep + (
            ("events_per_s", lambda r: r.perf["events_per_s"]),))
        row(f"simloop_event_N{n}", dt_ev * 1e6,
            f"coverage={ev['coverage']:.4f} t_full={ev['t_full']:.4f} "
            f"msgs={ev['msgs']} events_per_s={ev['events_per_s']:.0f}")
        if n == 1024:
            # observability rows (DESIGN.md §11), timed back-to-back
            # with the base event row (before the compiled run touches
            # the heap): obsoff re-runs the identical disabled-obs
            # scenario so its ratio against the base row bounds the
            # threaded-but-disabled probe cost (gated <= 2% by
            # benchmarks/check_obs.py --bench); the obs row measures
            # the metrics-enabled cost (reported, ungated). The gated
            # pair alternates base/obsoff and takes min-of-2 per side —
            # interference noise is one-sided (it only ever adds time),
            # so min-of-k pairs far tighter than single shots.
            dt_off, off = timed(simloop_spec(n, "event", {}, 4), (
                ("events_per_s", lambda r: r.perf["events_per_s"]),))
            dt_ev = min(dt_ev, timed(simloop_spec(n, "event", {}, 4))[0])
            dt_off = min(dt_off,
                         timed(simloop_spec(n, "event", {}, 4))[0])
            row(f"simloop_event_N{n}_obsoff", dt_off * 1e6,
                f"overhead={dt_off / max(dt_ev, 1e-12):.4f} "
                f"events_per_s={off['events_per_s']:.0f}")
            spec_on = simloop_spec(n, "event", {}, 4)
            spec_on.obs.enabled = True
            dt_on, on = timed(spec_on, (
                ("scalars", lambda r: len(r.metrics.scalars)),
                ("series", lambda r: len(r.metrics.series))))
            row(f"simloop_event_N{n}_obs", dt_on * 1e6,
                f"overhead={dt_on / max(dt_ev, 1e-12):.4f} "
                f"scalars={on['scalars']} series={on['series']}")
        dt_co, co = timed(simloop_spec(n, "compiled", {"tick": 0.05}, 4),
                          scalar_keep + (
            ("n_ticks", lambda r: r.perf["n_ticks"]),
            ("scan_s", lambda r: r.perf["phases"]["scan_s"])))
        row(f"simloop_compiled_N{n}", dt_co * 1e6,
            f"coverage={co['coverage']:.4f} t_full={co['t_full']:.4f} "
            f"msgs={co['msgs']} "
            f"speedup={dt_ev / max(dt_co, 1e-12):.2f} "
            f"ticks={co['n_ticks']} scan_s={co['scan_s']:.2f}")
    if smoke:
        return
    # full tier: the 10k-client fleet, compiled only, coarse 0.5s tick
    exp = Experiment.from_spec(simloop_spec(
        10_000, "compiled", {"tick": 0.5, "chunk_ticks": 16}, 8))
    exp.build()
    sw = Stopwatch().start()
    r = exp.run()
    dt = sw.stop()
    row("simloop_compiled_N10000", dt * 1e6,
        f"coverage={r.coverage:.4f} t_full={r.t_full:.4f} "
        f"msgs={r.net['transport']['n_sent']} "
        f"ticks={r.perf['n_ticks']} "
        f"scan_s={r.perf['phases']['scan_s']:.2f}")


def bench_partition_fig4():
    """Fig 4: partition skew vs alpha."""
    from benchmarks.common import row
    from repro.data import dirichlet_partition
    from repro.data.partition import partition_stats
    labels = np.random.default_rng(0).integers(0, 10, 20000)
    ents = {}
    for alpha in (0.1, 0.3, 0.5):
        parts = dirichlet_partition(labels, 20, alpha, seed=0)
        c = partition_stats(labels, parts)["counts"]
        p = c / np.maximum(c.sum(1, keepdims=True), 1)
        ents[alpha] = float(-(p * np.log(p + 1e-12)).sum(1).mean())
    row("fig4_partition_entropy", 0.0,
        " ".join(f"alpha{a}={e:.2f}" for a, e in ents.items()))


def bench_roofline_summary():
    """Dry-run roofline: dominant bottleneck per (arch, shape), 16x16 mesh."""
    from benchmarks.common import row
    try:
        from repro.roofline import analyze_all
        rows = analyze_all(mesh="16x16")
    except Exception as e:  # noqa: BLE001
        row("roofline", 0.0, f"unavailable ({type(e).__name__})")
        return
    if not rows:
        row("roofline", 0.0, "no dry-run results yet (run launch/dryrun.py)")
        return
    for r in rows:
        row(f"roofline_{r['arch']}_{r['shape']}",
            r["step_lower_bound_s"] * 1e6,
            f"dominant={r['dominant']} useful={r['useful_ratio'] or 0:.2f}")


# single-suite entries runnable in isolation via --only (each accepts
# the smoke flag); CI runs `--only simloop` as its own gated step so the
# event-vs-compiled comparison gets a dedicated JSON artifact
ONLY = {"simloop": bench_simloop, "faults": bench_faults,
        "serve": bench_serve}


def main(smoke: bool = False, json_path: str = None,
         only: str = None) -> None:
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    print("name,us_per_call,derived")
    if only:
        ONLY[only](smoke=smoke)
    else:
        if not smoke:
            local_acc, res = bench_table1_accuracy()
            bench_table2_negative_transfer(local_acc, res)
            bench_table3_scalability()
        bench_table4_cost()
        bench_selection_throughput()
        bench_select_incremental(smoke=smoke)
        bench_gossip_scale()
        bench_lossy_repair()
        bench_faults(smoke=smoke)
        bench_serve(smoke=smoke)
        bench_nsga2_microbench()
        bench_ensemble_fitness_kernel()
        bench_partition_fig4()
        if not smoke:
            bench_simloop(smoke=False)
            bench_roofline_summary()
    if json_path:
        import json
        from benchmarks.common import ROWS
        with open(json_path, "w") as f:
            json.dump(ROWS, f, indent=2, allow_nan=False)
        print(f"# wrote {len(ROWS)} rows to {json_path}")


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="fast CI subset: skip the model-training tables")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also dump all rows as a JSON array (CI artifact)")
    ap.add_argument("--only", default=None, choices=sorted(ONLY),
                    help="run a single benchmark suite in isolation")
    args = ap.parse_args()
    main(args.smoke, args.json, args.only)
