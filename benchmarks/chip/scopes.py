"""From a profiler trace to device seconds per program scope, and the
device's idle gaps named by the innermost host span, the program's own
spans included.

The program names its device work with `jax.named_scope` (`flush`,
`flush/scatter`, `flush/gram`, `gather`, `ga`, `ga/init`, `ga/eval`,
`ga/rank`, `ga/breed`, `ga/survive`, `vote`), which XLA keeps in each
instruction's `op_name` metadata, and its host work with
`Metrics.span` (`store.flush*`, `engine.select*`), which also writes a
`TraceAnnotation` beside the harness's own spans (`trace.SPANS`).

`read_xplane` turns the `.xplane.pb` into plain lists: per device plane,
(op, scope or None, start_ns, end_ns) of each operation on its op line;
on the host, every harness and program span. A TPU trace names an op by
its HLO instruction only, so its scope comes from the op names of the
compiled programs (`hlo_op_names` of `compiled.as_text()`), keyed by the
module the op ran in (the trace's `XLA Modules` line) and the
instruction. An instruction outside every scope (a layout copy the
compiler adds, a parameter's copy named after the parameter) takes the
op name of the scoped instructions that use it, where they lie under
one top-level scope, else of those it uses; what is left takes its
module's scope where every named instruction of the module lies under
one top-level scope (`_flush` is all `flush`). `device_scopes` and
`program_gaps` work on those lists only, so they are checked on
hand-made ones.
"""
from __future__ import annotations

import bisect
import heapq
import re
from collections import defaultdict

from chip import trace

# the program's device scopes: top level -> its second-level children
SCOPES = {"flush": ("scatter", "gram"), "gather": (),
          "ga": ("init", "eval", "rank", "breed", "survive"), "vote": ()}
UNSCOPED = "unscoped"
NO_SPAN = "no_span"
# the program's host spans (repro.obs Metrics.span names)
PROGRAM_SPANS = ("store.flush", "engine.select")
# the device plane's line with one event per program run
MODULE_LINE = "XLA Modules"
_HLO_MODULE = re.compile(r"^HloModule\s+([^\s,]+)", re.M)
_HLO_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$", re.M)
_HLO_OP_NAME = re.compile(r"metadata=\{[^}]*op_name=\"([^\"]*)\"")
_HLO_CALL = re.compile(r"\s[\w\-]+\(")
_HLO_REF = re.compile(r"%([\w.\-]+)")


def scope_of(path: str):
    """The program scope of one op name path, two levels at most:
    "jit(select)/ga/while/body/closed_call/eval/dot_general" -> "ga/eval",
    "jit(select)/vote/vmap()/gather" -> "vote"; None outside every scope.
    The last component is the primitive (a `gather` primitive is not the
    `gather` scope)."""
    names = path.split("/")[:-1] if path else []
    top = next((n for n in names if n in SCOPES), None)
    if top is None:
        return None
    rest = names[names.index(top) + 1:]
    sub = next((n for n in rest if n in SCOPES[top]), None)
    return top if sub is None else f"{top}/{sub}"


def _operands(rhs: str) -> list:
    """The instructions one HLO instruction's right-hand side reads: the
    names inside the parentheses of its opcode."""
    call = _HLO_CALL.search(rhs)
    if call is None:
        return []
    depth, i = 1, call.end()
    for j in range(i, len(rhs)):
        depth += {"(": 1, ")": -1}.get(rhs[j], 0)
        if depth == 0:
            return _HLO_REF.findall(rhs[i:j])
    return []


def _inherit(named: dict, uses: dict) -> dict:
    """Give each instruction outside every scope the op name of the
    scoped instructions that use it, where those lie under one top-level
    scope, else of the instructions it uses that carry a scope of their
    own; repeated until nothing changes, so a chain of copies and
    bitcasts that ends in a scoped instruction is named too."""
    users = defaultdict(list)
    for instr, ops in uses.items():
        for op in ops:
            users[op].append(instr)
    names = dict(named)

    def one(candidates, names):
        found = [names[c] for c in candidates if scope_of(names.get(c, ""))]
        tops = {scope_of(p).split("/")[0] for p in found}
        return found[0] if len(tops) == 1 else None

    changed = True
    while changed:
        changed = False
        for instr in uses:
            if scope_of(names.get(instr, "")) is not None:
                continue
            path = one(users[instr], names) or one(uses[instr], named)
            if path is not None:
                names[instr] = path
                changed = True
    return names


def hlo_op_names(text: str) -> dict:
    """(module, instruction) -> op_name of one compiled program's HLO text
    (`compiled.as_text()`). An instruction outside every scope takes the
    op name of its scoped neighbours (`_inherit`); one with no op name
    and no scoped neighbour is left out."""
    m = _HLO_MODULE.search(text)
    if m is None:
        return {}
    named, uses = {}, {}
    for instr, rhs in _HLO_INSTR.findall(text):
        uses[instr] = _operands(rhs)
        op = _HLO_OP_NAME.search(rhs)
        if op is not None:
            named[instr] = op.group(1)
    return {(m.group(1), instr): path
            for instr, path in _inherit(named, uses).items()}


def _module_scopes(op_names: dict) -> dict:
    """module -> its top-level scope, where it has exactly one."""
    tops = defaultdict(set)
    for (module, _), path in op_names.items():
        sc = scope_of(path)
        if sc is not None:
            tops[module].add(sc.split("/")[0])
    return {m: next(iter(t)) for m, t in tops.items() if len(t) == 1}


def read_xplane(path: str, op_names: dict) -> dict:
    """{"devices": {plane: [(op, scope, start_ns, end_ns)]},
    "host": [(span, start_ns, end_ns)] (harness and program spans),
    "mapped": share of op time whose instruction `op_names` knows}"""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    home = _module_scopes(op_names)
    devices, host, known, total = {}, [], 0, 0
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {ln.name: ln for ln in plane.lines}
            if trace.OP_LINE not in lines:
                continue
            mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                           ev.name.split("(", 1)[0])
                          for ev in (lines[MODULE_LINE].events
                                     if MODULE_LINE in lines else ()))
            starts = [m[0] for m in mods]
            ops = []
            for ev in lines[trace.OP_LINE].events:
                op = trace.op_name(ev.name)
                i = bisect.bisect_right(starts, ev.start_ns) - 1
                module = (mods[i][2] if i >= 0 and ev.start_ns < mods[i][1]
                          else "")
                found = op_names.get((module, op))
                total += ev.duration_ns
                known += ev.duration_ns if found is not None else 0
                sc = scope_of(found or "") or home.get(module)
                ops.append((op, sc, ev.start_ns,
                            ev.start_ns + ev.duration_ns))
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                         for ev in ln.events if _is_span(ev.name)]
    return {"devices": devices, "host": host,
            "mapped": known / total if total else None}


def _is_span(name: str) -> bool:
    return name in trace.SPANS or name.startswith(PROGRAM_SPANS)


def _window(host):
    windows = [(s, e) for n, s, e in host if n == trace.WINDOW]
    if not windows:
        raise ValueError("the trace holds no harness window span")
    return windows[0]


def _owners(items, lo, hi):
    """[lo, hi] cut at every boundary of `items` ((label, start, end)),
    as (a, b, label of the innermost item covering [a, b] or None). The
    innermost is the one that began last (then the one that ends first):
    an op inside a `while`, a span inside its parent."""
    items = sorted((it for it in items if it[2] > lo and it[1] < hi),
                   key=lambda it: (it[1], it[2]))
    points = sorted({lo, hi} | {x for _, s, e in items for x in (s, e)
                                if lo < x < hi})
    out, heap, i = [], [], 0
    for a, b in zip(points, points[1:]):
        while i < len(items) and items[i][1] <= a:
            heapq.heappush(heap, (-items[i][1], items[i][2], i))
            i += 1
        while heap and heap[0][1] <= a:
            heapq.heappop(heap)
        out.append((a, b, items[heap[0][2]][0] if heap else None))
    return out


def device_scopes(tr: dict) -> list:
    """[[scope, seconds]] per top-level scope, per second-level scope and
    `unscoped`, averaged over devices, largest first. A scope's time is
    the union of its ops' intervals inside the window; a container op
    (while, conditional, call) counts only where no op inside it runs:
    each instant goes to the innermost op running then."""
    w0, w1 = _window(tr["host"])
    devs = sorted(tr["devices"])
    if not devs:
        raise ValueError("the trace holds no device plane")
    total = defaultdict(float)
    for d in devs:
        for a, b, sc in _owners([(sc or UNSCOPED, s, e) for _, sc, s, e
                                 in tr["devices"][d]], w0, w1):
            if sc is None:
                continue                 # idle
            total[sc.split("/")[0]] += (b - a) / len(devs)
            if "/" in sc:
                total[sc] += (b - a) / len(devs)
    return [[k, v * 1e-9] for k, v in
            sorted(total.items(), key=lambda kv: -kv[1]) if v > 0]


def program_gaps(tr: dict) -> list:
    """[[span, seconds]]: each idle instant of the first device inside
    the window goes to the innermost host span covering it (the one
    that began last), harness or program, else to `no_span`."""
    w0, w1 = _window(tr["host"])
    devs = sorted(tr["devices"])
    if not devs:
        raise ValueError("the trace holds no device plane")
    busy = trace.union([(max(s, w0), min(e, w1))
                        for _, _, s, e in tr["devices"][devs[0]]
                        if e > w0 and s < w1])
    spans = [it for it in tr["host"] if it[0] != trace.WINDOW]
    idle, j = defaultdict(float), 0
    for a, b, name in _owners(spans, w0, w1):
        # the part of [a, b] that no busy interval covers
        while j < len(busy) and busy[j][1] <= a:
            j += 1
        free, k, cur = 0, j, a
        while k < len(busy) and busy[k][0] < b:
            free += max(0, busy[k][0] - cur)
            cur = max(cur, busy[k][1])
            k += 1
        free += max(0, b - cur)
        idle[name or NO_SPAN] += free
    return [[k, v * 1e-9] for k, v in
            sorted(idle.items(), key=lambda kv: -kv[1]) if v > 0]
