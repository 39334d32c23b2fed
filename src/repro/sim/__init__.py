"""Declarative experiment layer (DESIGN.md §9): `ExperimentSpec` — one
serializable, seed-complete description of any FedPAE scenario — and
`Experiment`, the single entry point that builds and runs it.

    from repro.sim import Experiment, ExperimentSpec

    spec = ExperimentSpec.from_json(open("exp.json").read())
    result = Experiment.from_spec(spec).run()

Components (transports, gossip protocols, churn models, repair loops,
train-cost models, message sizers) are tagged configs resolved by name
through `repro.sim.registry`; importing this package registers the stock
set (`repro.sim.build`).
"""
from repro.sim import build as _build  # noqa: F401  (registers components)
from repro.sim.experiment import Experiment, RunResult
from repro.sim.registry import known, register, resolve
from repro.sim.spec import (ComponentSpec, DataSpec, ExperimentSpec,
                            FaultSpec, NetworkSpec, ObsSpec, ScheduleSpec,
                            SelectionSpec, ServeSpec, TrainSpec)

__all__ = [
    "ComponentSpec", "DataSpec", "Experiment", "ExperimentSpec",
    "FaultSpec", "NetworkSpec", "ObsSpec", "RunResult", "ScheduleSpec",
    "SelectionSpec", "ServeSpec", "TrainSpec", "known", "register",
    "resolve",
]
