"""Device time of the program's named phases, read from a profiler trace.

The program names its phases with `jax.named_scope` (`store.*` in
`core/daemon_store.py`, `desim.*` in `sim/desim.py`). A scope reaches the
optimized HLO's `op_name` metadata, but a TPU trace's ops carry only the
instruction's text (`%while.210 = ... while(...)`). So the link from an
op to its scope comes from the HLO of the programs live in this process,
and the times come from the trace.

The table maps each instruction, named as `trace_reduce.short_name`
names a trace op, to its `op_name`. It is built from the executables
live when a reader asks, and kept while that set stays the same, so a
traced run builds it once. An instruction name that two live programs
define with different `op_name`s is left out: the trace cannot say which
program ran it, so its time counts as unscoped.
"""
from __future__ import annotations

import re

from trace_reduce import (_clip, _union, reduce_busy, scan_steps,
                          short_name)

_INSTRUCTION = re.compile(r"%\S+ = ")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
# a transform's wrapper around a name-stack entry: `vmap(store.serve)`
_WRAPPED = re.compile(r"^[A-Za-z_]\w*\((.*)\)$")

_TABLE = {"key": None, "table": {}}


def components(op_name: str) -> set:
    """The path components of an `op_name`, each transform wrapper
    (`vmap(...)`, `jit(...)`) also unwrapped to the name it holds."""
    out = set()
    for part in op_name.split("/"):
        while part:
            out.add(part)
            m = _WRAPPED.match(part)
            part = m.group(1) if m else ""
    return out


def build_table(hlo_texts) -> dict:
    """{short name: op_name} over the instructions of the given HLO
    module texts; a name defined with two different `op_name`s maps to
    None (unattributed)."""
    table = {}
    for text in hlo_texts:
        for line in text.splitlines():
            line = line.strip()
            if line.startswith("ROOT "):
                line = line[5:]
            if not _INSTRUCTION.match(line):
                continue
            key = short_name(line)
            m = _OP_NAME.search(line)
            op = m.group(1) if m else ""
            if key in table and table[key] != op:
                table[key] = None
            else:
                table[key] = op
    return table


def live_table() -> dict:
    """The table of the live executables, rebuilt only when the set of
    live executables changed since the last call."""
    from jax.extend.backend import get_backend
    execs = get_backend().live_executables()
    key = tuple(sorted(id(e) for e in execs))
    if _TABLE["key"] != key:
        _TABLE["table"] = build_table(m.to_string() for ex in execs
                                      for m in ex.hlo_modules())
        _TABLE["key"] = key
    return _TABLE["table"]


def _scoped_s(tr: dict, match, table) -> float | None:
    """Seconds of the union of the window-clipped ops whose `op_name`
    components satisfy `match`, averaged over the chips; None where no
    op matched. A `while` and the ops of its body overlap; the union
    counts that time once."""
    w0, w1 = tr["window"]
    hit, per, found = {}, [], False
    for evs in tr["device"].values():
        ivs = []
        for ev in evs:
            if ev[0] not in hit:
                op = table.get(short_name(ev[0]))
                hit[ev[0]] = bool(op) and match(components(op))
            if hit[ev[0]]:
                c = _clip(ev, w0, w1)
                if c:
                    ivs.append(c)
        found = found or bool(ivs)
        per.append(_union(ivs))
    if not found:
        return None
    return sum(per) / len(per) / 1e9


def scope_s(tr: dict, scope: str, table: dict = None) -> float | None:
    """Device seconds under the named scope `scope` (an `op_name` path
    component), a scope nested in it included, averaged over the chips;
    None where no op of the trace is under it."""
    table = live_table() if table is None else table
    return _scoped_s(tr, lambda c: scope in c, table)


def unscoped_pct(tr: dict, prefix: str, table: dict = None) -> float | None:
    """100 x (busy - the union of all ops under any scope whose name
    starts with `prefix`) / busy; None where no op is under such a
    scope (a program without the scopes)."""
    table = live_table() if table is None else table
    scoped = _scoped_s(tr, lambda c: any(x.startswith(prefix) for x in c),
                       table)
    busy = reduce_busy(tr)["busy_s"]
    if scoped is None or busy <= 0:
        return None
    return 100.0 * (busy - scoped) / busy


def per_step_ms(tr: dict, ctx: dict, scope: str) -> float | None:
    """A store scope's device time ÷ the traced decode steps, in ms."""
    s = scope_s(tr, scope)
    if s is None or not ctx.get("units"):
        return None
    return s / ctx["units"] * 1e3


def per_scan_step_us(tr: dict, scope: str) -> float | None:
    """A simulator scope's device time ÷ the scan steps in the window
    (the divisor `sim.step_us` uses), in us."""
    s = scope_s(tr, scope)
    steps = scan_steps(tr)
    if s is None or not steps:
        return None
    return s / steps * 1e6
