"""End-to-end behaviour tests for FedPAE (the paper's claims, reduced scale).

These are the integration tests behind EXPERIMENTS.md: FedPAE must (a)
beat the non-personalized FL baseline under non-IID data, (b) not fall
meaningfully below the local-ensemble baseline (negative-transfer guard),
and (c) produce exactly-k ensembles biased toward local models as
heterogeneity rises.
"""
import numpy as np
import pytest

from repro.data import dirichlet_partition, make_synthetic_images, split_train_val_test
from repro.fl.client import ClientData
from repro.sim import (DataSpec, Experiment, ExperimentSpec, ScheduleSpec,
                       SelectionSpec, TrainSpec)


def _make_clients(n_clients=4, alpha=0.1, n=1800, n_classes=8, seed=0):
    ds = make_synthetic_images(n, n_classes, size=10, seed=seed)
    parts = dirichlet_partition(ds.y, n_clients, alpha, seed=seed)
    out = []
    for ix in parts:
        tr, va, te = split_train_val_test(ix, seed=seed + 1)
        out.append(ClientData(ds.x[tr], ds.y[tr], ds.x[va], ds.y[va],
                              ds.x[te], ds.y[te]))
    return out, n_classes


@pytest.fixture(scope="module")
def fedpae_run():
    datasets, n_classes = _make_clients()
    spec = ExperimentSpec(
        data=DataSpec(kind="external", n_clients=len(datasets),
                      n_classes=n_classes),
        train=TrainSpec(families=("cnn4", "vgg", "resnet"), max_epochs=10,
                        patience=4, width=12),
        selection=SelectionSpec(pop_size=32, generations=20, k=3,
                                ensemble_k=3),
        schedule=ScheduleSpec(mode="sync"), seed=0)
    exp = Experiment(spec, datasets=datasets)
    local_acc = exp.local_ensemble()
    res = exp.run()
    return datasets, spec, local_acc, res


def test_fedpae_beats_or_matches_local(fedpae_run):
    _, _, local_acc, res = fedpae_run
    assert res.test_acc.mean() >= local_acc.mean() - 0.03, \
        f"fedpae {res.test_acc.mean():.3f} << local {local_acc.mean():.3f}"


def test_fedpae_reasonable_absolute_accuracy(fedpae_run):
    _, _, _, res = fedpae_run
    assert res.test_acc.mean() > 0.5  # far above 1/8 chance


def test_ensembles_have_exact_k(fedpae_run):
    _, spec, _, res = fedpae_run
    for chrom in res.chromosomes:
        assert chrom.sum() == spec.selection.ensemble_k


def test_local_fraction_bounded(fedpae_run):
    _, _, _, res = fedpae_run
    assert ((res.local_frac >= 0) & (res.local_frac <= 1)).all()


def test_negative_transfer_bounded_per_client(fedpae_run):
    """Paper Table II: per-client FedPAE accuracy never falls far below
    that client's own local ensemble."""
    datasets, spec, local_acc, res = fedpae_run
    rel = (res.test_acc - local_acc) / np.maximum(local_acc, 1e-9)
    assert rel.min() > -0.12, f"negative transfer too large: {rel}"
