#!/usr/bin/env python3
"""Run FedPAE's main path once on one TPU chip and check what comes out.

    python3 chip_smoke.py              # on a machine with a TPU
    JAX_PLATFORMS=cpu python3 chip_smoke.py --rehearse   # tiny, any backend

Three phases, each built through `Experiment.from_spec(spec)`:

  paper_sync    the paper's cell at full width: 20 clients, 100 classes,
                32x32x3 synthetic images (60,000 samples, Dir(0.1)), the
                five CNN families at width 16, sync selection P=G=100,
                k=5. Cut: one local-training epoch instead of 60.
  async_select  examples/specs/serve_drift.json at full size with the
                Pallas fitness kernel: debounced re-selection, in-run
                dirty-slot flushes, serving, drift monitor.
  compiled      examples/specs/fleet_sweep.json at full size (2,048
                clients) on the compiled array-world backend.

Each phase prints one JSON line: set-up and run seconds (wall clock,
ending on host values or `block_until_ready`), the seconds XLA spent
compiling within them, and the checks with their numbers. Every phase
runs; if any check failed, the script exits 1. The last line is
{"ok": true, "device": {...}}, printed only on a TPU after every check
of every phase passed. `--rehearse` runs every phase at
a tiny size on whatever backend JAX has and never prints that line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

FAMILIES = ("cnn4", "vgg", "resnet", "densenet", "inception")


def _paper_spec(rehearse: bool) -> dict:
    spec = {
        "seed": 0,
        "data": {"kind": "synthetic_images", "n_clients": 20,
                 "n_classes": 100, "n_samples": 60000, "image_size": 32,
                 "channels": 3, "alpha": 0.1},
        "train": {"families": list(FAMILIES), "width": 16, "batch": 32,
                  "max_epochs": 1},
        "selection": {"pop_size": 100, "generations": 100, "k": 5},
        "schedule": {"mode": "sync"},
    }
    if rehearse:
        spec["data"].update(n_clients=3, n_classes=10, n_samples=900,
                            image_size=8)
        spec["train"].update(families=["cnn4", "vgg"], width=4)
        spec["selection"].update(pop_size=8, generations=2, k=2)
    return spec


def _file_spec(name: str, rehearse: bool, **overrides) -> dict:
    from repro.sim.run import apply_override
    with open(os.path.join(ROOT, "examples", "specs", name)) as f:
        raw = json.load(f)
    smoke = raw.pop("smoke_overrides", {})
    for path, value in (smoke.items() if rehearse else ()):
        apply_override(raw, path, value)
    for path, value in overrides.items():
        apply_override(raw, path, value)
    return raw


class _CompileClock:
    """Sums XLA's backend-compile seconds (a JAX monitoring event)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._hear)

    def _hear(self, event, duration, **_):
        if event == self.EVENT:
            self.total += duration


def _run_phase(name, raw, clock, checks_fn) -> list:
    """Build and run one spec; time both halves; print the phase line.
    Returns the checks that failed."""
    import jax
    from repro.obs.metrics import Stopwatch
    from repro.sim import Experiment, ExperimentSpec
    c0 = clock.total
    sw = Stopwatch().start()
    exp = Experiment.from_spec(ExperimentSpec.from_dict(raw)).build()
    if exp.engine is not None:
        jax.block_until_ready(exp.engine.device.preds)
    setup_s = sw.stop()
    c1 = clock.total
    sw.start()
    res = exp.run()
    if res.engine is not None:
        jax.block_until_ready((res.engine.device.acc, res.engine.device.S))
    run_s = sw.stop()
    compile_run = clock.total - c1
    numbers, failures = checks_fn(exp, res)
    print(json.dumps({"phase": name, "setup_s": setup_s, "run_s": run_s,
                      "compile_s": {"setup": c1 - c0, "run": compile_run},
                      "checks": numbers, "failed": failures},
                     allow_nan=False), flush=True)
    return failures


def _max_abs(a, b) -> float:
    import numpy as np
    return float(np.max(np.abs(np.asarray(a, np.float64)
                               - np.asarray(b, np.float64))))


def _max_ulps(a, b) -> float:
    """Largest difference in units of b's float32 last place."""
    import numpy as np
    b = np.asarray(b, np.float32)
    return float(np.max(np.abs(np.asarray(a, np.float64) - b)
                        / np.spacing(np.abs(b))))


def _s_tol(dev) -> float:
    """Float32 sums of the same V*C products taken in different orders
    differ by about sqrt(V*C) units of the last place."""
    import numpy as np
    return float(np.sqrt(dev.v_max * dev.n_classes)
                 * np.finfo(np.float32).eps)


def _paper_checks(k: int, n_classes: int, on_tpu: bool):
    def checks(exp, res):
        import jax
        import jax.numpy as jnp
        import numpy as np
        from repro.core.bench import stack_stores
        from repro.core.objectives import population_objectives
        from repro.core.selection import selection_stats
        from repro.kernels.ensemble_fitness.kernel import \
            ensemble_fitness_batched
        engine, dev = res.engine, res.engine.device
        n = len(res.stores)
        s_tol = _s_tol(dev)
        sizes = [int((engine.results[c]["chromosome"] > 0.5).sum())
                 for c in range(n)]
        platforms = sorted({d.platform for d in dev.preds.devices()})
        # the same stores, rebuilt in one shot on the chip and on the host
        preds, labels, _ = stack_stores(res.stores, v_to=dev.v_max)
        cpu = jax.devices("cpu")[0]
        acc_c, S_c = selection_stats(jnp.asarray(preds), jnp.asarray(labels))
        acc_h, S_h = selection_stats(jax.device_put(preds, cpu),
                                     jax.device_put(labels, cpu))
        # one population scored by the compiled kernel and by jnp
        pop = jnp.asarray(np.stack([engine.results[c]["pop"]
                                    for c in range(n)]))
        kern = jax.jit(ensemble_fitness_batched,
                       static_argnames=("interpret",))
        lowered = kern.lower(pop, dev.acc, dev.S, interpret=not on_tpu)
        mosaic = "tpu_custom_call" in lowered.compile().as_text()
        st_k, dv_k = kern(pop, dev.acc, dev.S, interpret=not on_tpu)
        st_j, dv_j = jax.jit(jax.vmap(population_objectives))(
            pop, dev.acc, dev.S)
        nums = {
            "n_clients": n, "store_shape": list(dev.preds.shape),
            "ensemble_sizes": sizes,
            "test_acc_mean": float(np.mean(res.test_acc)),
            "chance": 1.0 / n_classes, "preds_platforms": platforms,
            "acc_inc_vs_chip_rebuild": _max_abs(dev.acc, acc_c),
            "S_inc_vs_chip_rebuild": _max_abs(dev.S, S_c),
            "acc_inc_vs_cpu_rebuild_ulps": _max_ulps(dev.acc, acc_h),
            "S_inc_vs_cpu_rebuild": _max_abs(dev.S, S_h),
            "S_chip_vs_cpu_rebuild": _max_abs(S_c, S_h),
            "kernel_vs_jnp_strength": _max_abs(st_k, st_j),
            "kernel_vs_jnp_diversity": _max_abs(dv_k, dv_j),
            "kernel_tpu_custom_call": mosaic,
            "S_tol": s_tol,
        }
        fails = []
        if any(s != k for s in sizes):
            fails.append(f"an ensemble does not have k={k} members")
        if not nums["test_acc_mean"] > nums["chance"]:
            fails.append("mean test accuracy not above chance")
        if on_tpu and platforms != ["tpu"]:
            fails.append("the store's preds are not on the TPU")
        # acc is a hit count over nv: exact on one device; the chip's
        # float32 division may round the last place differently
        if nums["acc_inc_vs_chip_rebuild"]:
            fails.append("incremental acc differs from the chip rebuild")
        if not nums["acc_inc_vs_cpu_rebuild_ulps"] <= 1.0:
            fails.append("incremental acc over 1 ulp from the host rebuild")
        if on_tpu and not mosaic:
            fails.append("no Mosaic custom call in the compiled kernel")
        for key in ("S_inc_vs_chip_rebuild", "S_inc_vs_cpu_rebuild"):
            if not nums[key] <= s_tol:
                fails.append(f"{key} above {s_tol}")
        for key in ("kernel_vs_jnp_strength", "kernel_vs_jnp_diversity"):
            if not nums[key] <= 1e-5:
                fails.append(f"{key} above 1e-5")
        return nums, fails
    return checks


def _async_checks(on_tpu: bool):
    def checks(exp, res):
        from repro.core.device_store import DeviceStoreBatch
        from repro.kernels.ensemble_fitness import ops as ef_ops
        serve = res.net["serve"]
        # the stats the run maintained flush by flush, against one flush
        # of the same final stores into a fresh mirror
        dev = res.engine.device
        dev.flush()
        fresh = DeviceStoreBatch(res.stores, v_max=dev.v_max)
        fresh.flush()
        s_tol = _s_tol(dev)
        nums = {"n_selections": res.summary().get("n_selections"),
                "n_queries": serve["n_queries"],
                "n_reselections": serve["n_reselections"],
                "kernel_compiled": not ef_ops._interpret(),
                "select_batch_widths": sorted({b for _, b in
                                               res.select_batches}),
                "n_flushes": dev.n_flushes,
                "acc_inc_vs_one_shot": _max_abs(dev.acc, fresh.acc),
                "S_inc_vs_one_shot": _max_abs(dev.S, fresh.S),
                "S_tol": s_tol}
        fails = []
        if not serve["n_reselections"] > 0:
            fails.append("the monitor fired no re-selection")
        if not serve["n_queries"] > 0:
            fails.append("no query was answered")
        if on_tpu and not nums["kernel_compiled"]:
            fails.append("the fitness kernel ran in interpret mode")
        if nums["acc_inc_vs_one_shot"]:
            fails.append("incremental acc differs from a one-shot flush")
        if not nums["S_inc_vs_one_shot"] <= s_tol:
            fails.append(f"S_inc_vs_one_shot above {s_tol}")
        return nums, fails
    return checks


def _compiled_checks(n_clients: int):
    def checks(exp, res):
        s = res.summary()
        nums = {"n_clients": s["n_clients"], "coverage": s["coverage"],
                "backend": s["perf"]["backend"]}
        fails = []
        if s["coverage"] != 1.0 or s["n_clients"] != n_clients:
            fails.append(f"coverage {s['coverage']} at {s['n_clients']} "
                         f"clients, want 1.0 at {n_clients}")
        return nums, fails
    return checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any backend; prints no ok line")
    args = ap.parse_args(argv)

    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    import jax
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.rehearse:
        print(f"chip_smoke: no TPU found (JAX sees {device}); "
              "nothing was run", file=sys.stderr)
        return 1

    clock = _CompileClock()
    paper = _paper_spec(args.rehearse)
    fleet = _file_spec("fleet_sweep.json", args.rehearse)
    failed = _run_phase("paper_sync", paper, clock,
                        _paper_checks(paper["selection"]["k"],
                                      paper["data"]["n_classes"], on_tpu))
    failed += _run_phase("async_select",
                         _file_spec("serve_drift.json", args.rehearse,
                                    **{"selection.use_kernel": True}),
                         clock, _async_checks(on_tpu))
    failed += _run_phase("compiled", fleet, clock,
                         _compiled_checks(fleet["data"]["n_clients"]))
    if failed:
        print(f"chip_smoke: failed checks: {failed}", file=sys.stderr)
        return 1
    if args.rehearse:
        print(json.dumps({"rehearsal": True, "device": device},
                         allow_nan=False))
    else:
        print(json.dumps({"ok": True, "device": device}, allow_nan=False))
    return 0


if __name__ == "__main__":
    sys.exit(main())
