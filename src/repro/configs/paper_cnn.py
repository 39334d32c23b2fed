"""The paper's own experimental configuration: 20 clients, 5 CNN families,
Dir(alpha) partitions of an image-classification dataset, NSGA-II with
population 100 x 100 generations, ensemble size k=5.

(CIFAR-10/100 are not available offline; the data layer substitutes the
synthetic generator — DESIGN.md §2. Scale knobs are reduced-by-default so
the benchmark suite completes on one CPU core; pass full=True for the
paper-faithful sizes.)

A preset holds the sweep axes (`n_clients`, `n_samples`, `alphas`,
`datasets`) beside the spec sections every run shares (`train`,
`selection`); `spec` turns one grid cell into an `ExperimentSpec`.
"""
from repro.sim.spec import (DataSpec, ExperimentSpec, ScheduleSpec,
                            SelectionSpec, TrainSpec)


def config(full: bool = False):
    if full:
        return {
            "n_clients": 20,
            "n_samples": 60000,
            "alphas": (0.5, 0.3, 0.1),
            "datasets": {"synthetic10": 10, "synthetic100": 100},
            "train": TrainSpec(
                families=("cnn4", "vgg", "resnet", "densenet", "inception"),
                max_epochs=60, patience=8),
            "selection": SelectionSpec(pop_size=100, generations=100, k=5,
                                       ensemble_k=5),
        }
    return {
        "n_clients": 8,
        "n_samples": 6000,
        "alphas": (0.5, 0.3, 0.1),
        "datasets": {"synthetic10": 10},
        "train": TrainSpec(families=("cnn4", "vgg", "resnet"),
                           max_epochs=15, patience=5, width=12),
        "selection": SelectionSpec(pop_size=48, generations=40, k=3,
                                   ensemble_k=3),
    }


def smoke():
    cfg = config()
    cfg.update(n_clients=3, n_samples=900)
    return cfg


def spec(pc: dict, n_classes: int, seed: int = 0, **data) -> ExperimentSpec:
    """One synchronous run of preset `pc` (the paper's Table I-IV
    protocol). `data` overrides the DataSpec, whose default is
    `pc["n_clients"]` caller-injected clients (kind "external")."""
    return ExperimentSpec(
        data=DataSpec(**{"kind": "external", "n_clients": pc["n_clients"],
                         "n_classes": n_classes, **data}),
        train=pc["train"], selection=pc["selection"],
        schedule=ScheduleSpec(mode="sync"), seed=seed)
