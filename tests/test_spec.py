"""Spec-layer tests (DESIGN.md §9): serialization round-trips, strict
unknown-name/field errors, registry resolution, the paper presets, and
the golden-trace guarantee — a run with caller-injected collaborators
and the pure spec path produce bit-identical traces for the same
scenario and seed."""
import dataclasses
import json

import numpy as np
import pytest

from repro.configs import paper_cnn
from repro.fl.topology import make_topology
from repro.p2p.params import check_params
from repro.p2p import (AntiEntropyRepair, ChurnConfig, ChurnSchedule,
                       GossipConfig, GossipProtocol, GossipTransport,
                       RepairConfig, TransportConfig,
                       prediction_matrix_bytes)
from repro.sim import (ComponentSpec, DataSpec, Experiment, ExperimentSpec,
                       NetworkSpec, ScheduleSpec, SelectionSpec, TrainSpec,
                       register, resolve)
from repro.sim.build import build_client_datasets
from repro.sim.run import apply_override


def lossy_churn_spec(n=8, n_classes=4):
    """The 8-client lossy+churn scenario the golden-trace test drives."""
    return ExperimentSpec(
        data=DataSpec(kind="synthetic_images", n_clients=n,
                      n_classes=n_classes, n_samples=640, image_size=8,
                      alpha=0.5),
        train=TrainSpec(families=("cnn4",), width=8, max_epochs=2,
                        patience=2),
        selection=SelectionSpec(pop_size=8, generations=2, k=3,
                                ensemble_k=3),
        network=NetworkSpec(
            topology="ring",
            transport=ComponentSpec("gossip", {
                "base_latency": 0.05, "jitter": 1.0, "drop_prob": 0.2,
                "inbox_capacity": 32,
                "sizer": {"name": "prediction_matrix",
                          "params": {"n_val": 64,
                                     "n_classes": n_classes}}}),
            gossip="push",
            churn=ComponentSpec("lognormal", {
                "availability_beta": 0.2, "join_spread": 1.0,
                "leave_prob": 0.2}),
            repair=ComponentSpec("anti_entropy", {"max_rounds": 30,
                                                  "max_attempts": 6})),
        schedule=ScheduleSpec(mode="async"),
        seed=0)


# ---- serialization ----------------------------------------------------

def test_spec_dict_roundtrip():
    spec = lossy_churn_spec()
    assert ExperimentSpec.from_dict(spec.to_dict()) == spec


def test_spec_json_roundtrip():
    spec = lossy_churn_spec()
    via_json = ExperimentSpec.from_json(spec.to_json())
    assert via_json == spec
    # and the JSON itself is pure-JSON (no tuples, dataclasses, numpy)
    json.loads(spec.to_json())


def test_default_spec_roundtrip():
    spec = ExperimentSpec()
    assert ExperimentSpec.from_dict(spec.to_dict()) == spec


def test_component_shorthand_forms():
    net = NetworkSpec(gossip="push_pull",
                      churn={"name": "lognormal",
                             "params": {"leave_prob": 0.1}})
    assert net.gossip == ComponentSpec("push_pull")
    assert net.churn == ComponentSpec("lognormal", {"leave_prob": 0.1})


def test_unknown_spec_field_raises():
    with pytest.raises(ValueError, match="bogus"):
        ExperimentSpec.from_dict({"data": {"bogus": 1}})
    with pytest.raises(ValueError, match="unknown spec field"):
        ExperimentSpec.from_dict({"not_a_section": {}})


def test_unknown_data_kind_and_mode_raise():
    with pytest.raises(ValueError, match="unknown data kind"):
        DataSpec(kind="martian")
    with pytest.raises(ValueError, match="unknown schedule mode"):
        ScheduleSpec(mode="yearly")


def test_apply_override_dotted_paths():
    d = lossy_churn_spec().to_dict()
    apply_override(d, "data.n_clients", 4)
    apply_override(d, "network.transport.params.drop_prob", 0.5)
    spec = ExperimentSpec.from_dict(d)
    assert spec.data.n_clients == 4
    assert spec.network.transport.params["drop_prob"] == 0.5


def test_apply_override_expands_shorthand_components():
    # a hand-written spec file may use the shorthand "gossip": "push";
    # overriding into it must keep the component name, not drop it
    d = {"network": {"gossip": "push"}}
    apply_override(d, "network.gossip.params.fanout", 2)
    spec = ExperimentSpec.from_dict(d)
    assert spec.network.gossip == ComponentSpec("push", {"fanout": 2})
    # descending through a scalar that is NOT a shorthand is a path error
    with pytest.raises(ValueError, match="not a section"):
        apply_override({"seed": 3}, "seed.nested", 1)


# ---- registry ---------------------------------------------------------

def test_unknown_component_name_lists_registered():
    spec = lossy_churn_spec()
    spec.network.transport = ComponentSpec("warp_drive")
    with pytest.raises(ValueError, match="unknown transport component "
                                         "'warp_drive'.*gossip"):
        Experiment.from_spec(spec).build()
    with pytest.raises(ValueError, match="'push'"):
        resolve("gossip", "shout")


def test_unknown_component_param_raises():
    spec = lossy_churn_spec()
    spec.network.churn = ComponentSpec("lognormal", {"beta_typo": 0.1})
    with pytest.raises(ValueError, match="beta_typo"):
        Experiment.from_spec(spec).build()


def test_unknown_train_cost_and_sizer_params_raise():
    spec = lossy_churn_spec()
    spec.schedule.train_cost = ComponentSpec("affine", {"slop": 9.9})
    with pytest.raises(ValueError, match="slop"):
        Experiment.from_spec(spec).build()
    spec = lossy_churn_spec()
    spec.network.transport = ComponentSpec(
        "gossip", {"sizer": {"name": "checkpoint",
                             "params": {"n_prams": 1}}})
    with pytest.raises(ValueError, match="n_prams"):
        Experiment.from_spec(spec).build()


def test_gossip_mode_in_params_rejected():
    # params carrying 'mode' could silently contradict the component
    # name the serialized spec advertises — reject it
    spec = lossy_churn_spec()
    spec.network.gossip = ComponentSpec("push", {"mode": "push_pull"})
    with pytest.raises(ValueError, match="mode"):
        Experiment.from_spec(spec).build()


def test_custom_component_registers_by_name():
    @register("train_cost", "quadratic_test_only")
    def _quad(params, ctx):
        check_params(params, ("a",), "train_cost[quadratic_test_only]")
        a = float(params.get("a", 1.0))
        return lambda c, m: a * (m + 1) ** 2

    spec = ExperimentSpec(
        data=DataSpec(kind="none", n_clients=4, n_classes=4, n_val=16,
                      models_per_client=2),
        selection=SelectionSpec(enabled=False),
        network=NetworkSpec(topology="ring"),
        schedule=ScheduleSpec(mode="async",
                              train_cost=ComponentSpec(
                                  "quadratic_test_only", {"a": 0.5})),
        seed=3)
    res = Experiment.from_spec(spec).run()
    # the quadratic cost shows up in the trained-event times: client c's
    # models finish at speed*0.5 and speed*(0.5 + 2.0), so the second
    # gap is exactly 4x the first regardless of the client's speed
    for c in range(4):
        t1, t2 = sorted(t for t, kind, cc, _ in res.trace.events
                        if kind == "trained" and cc == c)
        assert np.isclose((t2 - t1) / t1, 4.0)


# ---- experiment construction ------------------------------------------

def test_prediction_world_spec_runs_and_is_deterministic():
    spec = ExperimentSpec(
        data=DataSpec(kind="prediction_world", n_clients=6, n_classes=4,
                      n_val=32, models_per_client=2, seed=17),
        selection=SelectionSpec(pop_size=8, generations=2, k=3,
                                store_capacity=4),
        network=NetworkSpec(
            topology="ring",
            transport=ComponentSpec("gossip", {"drop_prob": 0.1}),
            gossip="push"),
        schedule=ScheduleSpec(mode="async", select_debounce=0.5,
                              train_cost=ComponentSpec(
                                  "affine", {"base": 1.0, "slope": 0.2})),
        seed=0)
    r1 = Experiment.from_spec(spec).run()
    r2 = Experiment.from_spec(
        ExperimentSpec.from_json(spec.to_json())).run()
    assert r1.trace.events == r2.trace.events
    assert r1.net == r2.net
    assert any(r1.selections[c] for c in range(6))
    assert r1.curve, "transport present => bytes-vs-acc curve recorded"
    # bounded stores: capacity 4 < 12 global models
    assert all(s.capacity == 4 for s in r1.stores)


def test_injected_collaborator_threads_into_spec_built_dependents():
    """An injected gossip must be the instance the spec-built repair
    reconciles — a crossed stack (repair around an orphaned spec-built
    gossip twin) would re-send against version vectors nobody updates."""
    n = 4
    spec = ExperimentSpec(
        data=DataSpec(kind="none", n_clients=n, n_classes=4, n_val=16,
                      models_per_client=1),
        selection=SelectionSpec(enabled=False),
        network=NetworkSpec(
            topology="ring",
            transport=ComponentSpec("gossip", {"drop_prob": 0.1}),
            gossip="push",
            repair=ComponentSpec("anti_entropy", {"max_rounds": 5})),
        schedule=ScheduleSpec(mode="async"), seed=0)
    mine = GossipProtocol(GossipConfig(mode="push", seed=0),
                          make_topology("ring", n, seed=0))
    exp = Experiment(spec, gossip=mine).build()
    assert exp.gossip is mine
    assert exp.repair is not None and exp.repair.gossip is mine


def test_external_kind_requires_datasets():
    spec = ExperimentSpec(data=DataSpec(kind="external", n_clients=2,
                                        n_classes=4))
    with pytest.raises(ValueError, match="external"):
        Experiment.from_spec(spec).build()


def test_sync_mode_requires_image_world():
    spec = ExperimentSpec(
        data=DataSpec(kind="prediction_world", n_clients=4, n_classes=4),
        schedule=ScheduleSpec(mode="sync"))
    with pytest.raises(ValueError, match="sync"):
        Experiment.from_spec(spec).build()


def test_sync_mode_rejects_network_components():
    # sync has no exchange simulation: silently ignoring a declared
    # transport would report a lossless run as the requested experiment
    spec = lossy_churn_spec()
    spec.schedule = ScheduleSpec(mode="sync")
    with pytest.raises(ValueError, match="transport"):
        Experiment.from_spec(spec).build()
    # the injection path must hit the same wall as the spec path
    sync_spec = ExperimentSpec(
        data=DataSpec(kind="synthetic_images", n_clients=2, n_classes=4,
                      n_samples=200, image_size=8, alpha=0.5),
        train=TrainSpec(families=("cnn4",), width=8, max_epochs=1,
                        patience=1),
        selection=SelectionSpec(pop_size=8, generations=2, k=1),
        schedule=ScheduleSpec(mode="sync"))
    mine = GossipProtocol(GossipConfig(mode="push", seed=0),
                          make_topology("ring", 2, seed=0))
    with pytest.raises(ValueError, match="injected collaborator"):
        Experiment(sync_spec, gossip=mine).build()


def test_run_is_single_shot():
    spec = ExperimentSpec(
        data=DataSpec(kind="none", n_clients=4, n_classes=4, n_val=16,
                      models_per_client=1),
        selection=SelectionSpec(enabled=False),
        network=NetworkSpec(topology="ring"),
        schedule=ScheduleSpec(mode="async"), seed=0)
    exp = Experiment.from_spec(spec)
    exp.run()
    with pytest.raises(RuntimeError, match="already ran"):
        exp.run()


# ---- golden trace: injected collaborators == spec-built ones ----------

def _external(spec, topology="full"):
    """The same scenario with caller-injected datasets: the data section
    names only the grid, the network only the topology."""
    return dataclasses.replace(
        spec, data=DataSpec(kind="external", n_clients=spec.data.n_clients,
                            n_classes=spec.data.n_classes),
        network=NetworkSpec(topology=topology))


def test_golden_trace_shim_vs_spec_lossy_churn():
    """The acceptance claim: the same 8-client lossy+churn scenario run
    from the spec alone and with caller-injected datasets and
    hand-constructed transport/gossip/churn/repair collaborators
    produces BIT-IDENTICAL traces for the same seed."""
    n, n_classes = 8, 4
    spec = lossy_churn_spec(n, n_classes)
    r_spec = Experiment.from_spec(spec).run()

    # the same scenario wired by hand
    datasets = build_client_datasets(spec.data, spec.seed)
    nb = make_topology("ring", n, seed=0)
    churn = ChurnSchedule(ChurnConfig(availability_beta=0.2,
                                      join_spread=1.0, leave_prob=0.2,
                                      seed=0), n)
    gossip = GossipProtocol(GossipConfig(mode="push", seed=0), nb,
                            churn=churn)
    transport = GossipTransport(
        TransportConfig(base_latency=0.05, jitter=1.0, drop_prob=0.2,
                        inbox_capacity=32, seed=0),
        n, lambda s, d, k: prediction_matrix_bytes(64, n_classes))
    repair = AntiEntropyRepair(
        RepairConfig(max_rounds=30, max_attempts=6, seed=0), gossip,
        churn=churn)
    r_injected = Experiment(_external(spec, "ring"), datasets=datasets,
                            transport=transport, gossip=gossip,
                            churn=churn, repair=repair).run()

    assert r_spec.trace.events == r_injected.trace.events
    assert r_spec.trace.net == r_injected.trace.net
    assert r_spec.trace.select_batches == r_injected.trace.select_batches
    assert np.array_equal(r_spec.test_acc, r_injected.test_acc)


def test_golden_sync_shim_vs_spec():
    """The sync twin of the golden-trace claim: a run with caller-injected
    datasets and the pure spec path agree bit-for-bit on accuracies,
    local fractions, and chromosomes for the same scenario and seed."""
    n, n_classes = 3, 4
    spec = ExperimentSpec(
        data=DataSpec(kind="synthetic_images", n_clients=n,
                      n_classes=n_classes, n_samples=360, image_size=8,
                      alpha=0.5),
        train=TrainSpec(families=("cnn4",), width=8, max_epochs=2,
                        patience=2),
        selection=SelectionSpec(pop_size=8, generations=2, k=2,
                                ensemble_k=2),
        schedule=ScheduleSpec(mode="sync"), seed=0)
    r_spec = Experiment.from_spec(spec).run()

    datasets = build_client_datasets(spec.data, spec.seed)
    r_injected = Experiment(_external(spec), datasets=datasets).run()

    assert np.array_equal(r_spec.test_acc, r_injected.test_acc)
    assert np.array_equal(r_spec.local_frac, r_injected.local_frac)
    assert all(np.array_equal(a, b) for a, b in
               zip(r_spec.chromosomes, r_injected.chromosomes))


def test_async_grid_mismatch_raises_valueerror_with_shapes():
    """Injected datasets must match the spec's client grid; the error
    names both counts."""
    datasets = build_client_datasets(lossy_churn_spec(4, 4).data, 0)
    with pytest.raises(ValueError) as ei:
        Experiment(lossy_churn_spec(7, 4), datasets=datasets)
    msg = str(ei.value)
    assert "(4 clients)" in msg and "n_clients=7" in msg


# ---- the paper presets (repro.configs.paper_cnn) -----------------------

@pytest.mark.parametrize("preset", ["full", "reduced", "smoke"])
def test_paper_cnn_preset_builds_spec(preset):
    pc = {"full": lambda: paper_cnn.config(full=True),
          "reduced": paper_cnn.config, "smoke": paper_cnn.smoke}[preset]()
    n_classes = max(pc["datasets"].values())
    for spec in (paper_cnn.spec(pc, n_classes),
                 paper_cnn.spec(pc, n_classes, seed=3,
                                kind="synthetic_images",
                                n_samples=pc["n_samples"],
                                alpha=pc["alphas"][0])):
        assert ExperimentSpec.from_json(spec.to_json()) == spec
        assert spec.data.n_clients == pc["n_clients"]
        assert spec.schedule.mode == "sync"
        exp = Experiment.from_spec(spec)
        assert exp.models_per_client == len(pc["train"].families)
    if preset == "full":  # the paper's own sizes
        sel = spec.selection
        assert pc["n_clients"] == 20
        assert spec.train.families == ("cnn4", "vgg", "resnet",
                                       "densenet", "inception")
        assert (sel.pop_size, sel.generations) == (100, 100)
        assert sel.k == sel.ensemble_k == 5
