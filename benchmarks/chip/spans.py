#!/usr/bin/env python3
"""One cell with the program's own spans and counters switched on: where
the store flush and the select spend their host time, what a flush
uploads, and which device scopes a batch is made of.

    python3 benchmarks/chip/spans.py --workload c100_round --seed 7 \
        --seconds 20 [--trace-seconds 3]

The cell is set up and checked as `run.py` does it (`run.run_cell`).
After warm-up the engine gets an enabled `repro.obs.Metrics` registry,
so the program's spans are on from there. A traced segment of split
batches runs before the window, and the readers of the span metrics
(`metrics/flush_host_ms.py` and its siblings) read it: host spans and
byte counters of the segment, device seconds per scope
(`scopes.device_scopes`) and idle gaps by innermost span
(`scopes.program_gaps`). A program without `Metrics.span` runs with its
spans off, and those metrics read null.

A TPU trace names each op by its HLO instruction only: after the traced
segment the cell's programs are compiled again for the device (the
compile cache's entries where the lowering matches) and their op names
give each instruction its scope (`scopes.hlo_op_names`).

The last line of standard output is one JSON object.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
from collections import Counter
from types import SimpleNamespace

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
TRACE_S = 3.0
# the per-layer metrics these spans, counters and scopes feed
READERS = ("flush_host_ms", "flush_upload_mb", "flush_useful_pct",
           "select_host_ms", "ga_device_ms", "vote_device_ms")


class CompileNames:
    """Backend compiles counted by the function that compiled (JAX's
    compile event carries `fun_name`)."""

    def __init__(self):
        import jax
        self.counts = Counter()
        jax.monitoring.register_event_duration_secs_listener(self._hear)

    def _hear(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.counts[kw.get("fun_name", "?")] += 1


def enable_spans(engine):
    """Give the engine an enabled registry; None where the program has
    no host spans (an older program: its spans stay off)."""
    from repro.obs.metrics import Metrics
    if not hasattr(Metrics, "span"):
        return None
    mx = Metrics()
    engine.metrics = mx
    return mx


def _counters(engine) -> dict:
    dev = engine.device
    return {k: getattr(dev, f"n_{k}", None)
            for k in ("bytes_uploaded", "bytes_useful")}


def compiled_op_names(wl, mix: dict) -> dict:
    """(module, instruction) -> op_name of the programs the cell's window
    runs (the flush, the batch gather, the GA stage), compiled for the
    device the workload's arrays live on."""
    import jax
    from chip import scopes
    from chip.compile_v5e import _pow2, programs
    from repro.core.device_store import _gather
    dev = wl.engine.device
    place = lambda s: jax.ShapeDtypeStruct(  # noqa: E731
        s.shape, s.dtype, sharding=dev.preds.sharding)
    arrays = (dev.preds, dev.labels, dev.masks, dev.acc, dev.S)
    gather = ("gather", _gather, arrays + (
        jax.ShapeDtypeStruct((_pow2(wl.kc),), jax.numpy.int32),), {})
    out = {}
    for _, fn, shapes, kw in programs(wl.cfg, mix) + [gather]:
        static = {k: v for k, v in kw.items()
                  if not isinstance(v, jax.ShapeDtypeStruct)}
        dyn = {k: place(v) for k, v in kw.items() if k not in static}
        text = fn.lower(*[place(s) for s in shapes], **dyn,
                        **static).compile().as_text()
        out.update(scopes.hlo_op_names(text))
    return out


def traced_segment(wl, mix: dict, mx, seconds: float,
                   on_chip: bool) -> SimpleNamespace:
    """A profiled run of split batches, read into what the span metrics'
    readers see: the segment's records and batch count, its closed
    spans, its byte-counter deltas, and (on a chip) the reduced trace
    with `device_scopes` and `program_gaps`."""
    import jax
    from chip import harness, run, scopes
    from chip import trace as trace_mod
    tdir = tempfile.mkdtemp(prefix="chipbench-spans-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    c0 = _counters(wl.engine)
    try:
        jax.profiler.start_trace(tdir, profiler_options=opts)
        try:
            t0 = harness.now()
            with jax.profiler.TraceAnnotation(trace_mod.WINDOW):
                records, _ = run._window(wl, seconds, True, harness.now)
            t1 = harness.now()
        finally:
            jax.profiler.stop_trace()
        reduced = None
        if on_chip:
            tr = scopes.read_xplane(trace_mod.find_xplane(tdir),
                                    compiled_op_names(wl, mix))
            # the harness's own reduction sees what run.py gives it
            flat = {"host": [h for h in tr["host"]
                             if h[0] in trace_mod.SPANS],
                    "devices": {d: [(op, s, e) for op, _, s, e in ops]
                                for d, ops in tr["devices"].items()}}
            reduced = dict(trace_mod.reduce(flat),
                           device_scopes=scopes.device_scopes(tr),
                           program_gaps=scopes.program_gaps(tr),
                           ops_mapped=tr["mapped"])
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    c1 = _counters(wl.engine)
    counters = (None if mx is None or c0["bytes_uploaded"] is None else
                {k: c1[k] - c0[k] for k in c0})
    spans = (None if mx is None else
             [r for r in mx.spans() if t0 <= r.start and r.end <= t1])
    return SimpleNamespace(records=records, n_traced=len(records),
                           spans=spans, counters=counters, trace=reduced,
                           unit=wl.unit)


def measure(workload: str, seed: int, seconds: float, *, t_start,
            trace_s: float = TRACE_S, overrides=None, log=None) -> dict:
    """One cell run with the program's spans on and a profiled segment
    before the window; the run's result with `span_metrics`,
    `traced_batches`, `breakdown` (on a chip), `span_totals` and
    `compiles_in_window` added."""
    import jax
    from chip import harness
    from chip.run import run_cell
    log = log or (lambda s: None)
    cell, _ = harness.find_cell(harness.manifest(), workload)
    mix = harness.load_traffic(cell["traffic"])
    names = CompileNames()
    got = {}
    on_chip = jax.devices()[0].platform != "cpu"

    def before_window(wl):
        mx = got["registry"] = enable_spans(wl.engine)
        got["segment"] = traced_segment(wl, mix, mx, trace_s, on_chip)
        got["compiles"] = Counter(names.counts)

    def after_check(wl, snap):
        got["compiles_in_window"] = dict(names.counts - got["compiles"])

    out = run_cell(workload, seed, seconds, False, t_start=t_start,
                   log=log, overrides=overrides, before_window=before_window,
                   after_check=after_check)
    out["spans_on"] = got["registry"] is not None
    out["compiles_in_window"] = got["compiles_in_window"]
    mx = got["registry"]
    if mx is not None:
        out["span_totals"] = {k: {"count": n, "total_s": t, "self_s": s}
                              for k, (n, t, s) in mx.span_totals().items()}
    seg = got["segment"]
    prefix = "round_" if cell["traffic"] == "reselect_round" else ""
    out["span_metrics"] = {
        prefix + n: harness.load_metric(n).read(seg) for n in READERS}
    out["traced_batches"] = seg.n_traced
    if seg.trace is not None:
        out["breakdown"] = {k: seg.trace[k] for k in
                            ("device_ops", "idle_gaps", "device_scopes",
                             "program_gaps", "ops_mapped")}
        out["device"].update(busy_s=seg.trace["busy_s"],
                             window_s=seg.trace["window_s"])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace-seconds", type=float, default=TRACE_S)
    args = ap.parse_args(argv)
    sys.path[:] = [p for p in sys.path
                   if os.path.abspath(p or os.curdir) != HERE]
    sys.path[:0] = [os.path.join(ROOT, "src"), BENCH]
    from chip import harness
    t_start = harness.now()
    harness.use_cache()
    import jax
    if jax.devices()[0].platform == "cpu":
        print("spans.py: no accelerator; nothing was run", file=sys.stderr)
        return 2

    def log(line):
        print(line, file=sys.stderr, flush=True)

    out = measure(args.workload, args.seed, args.seconds, t_start=t_start,
                  trace_s=args.trace_seconds, log=log)
    print(json.dumps(out, allow_nan=False), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
