"""Device milliseconds per execution of the XLA modules whose jit name
matches ``module`` (a regular expression), spent in the operations whose
named scope matches ``scope`` (another): what one tier, level or probe of
a program costs.  The scope is what ``jax.named_scope`` left on each
operation (``trace_spans`` says where the capture keeps it).  A program
without such a scope (an older tree's, or one the persistent cache handed
over from it) has nothing to read: None.

The whole table, every scope of the matching modules with its seconds
and share, goes to standard error once a run."""

import re
import sys

import trace_spans

_tables: dict = {}  # (id of a loaded capture, module) -> table()


def table(data: dict, module: str) -> tuple:
    """``({scope: ns}, executions)`` of the modules matching ``module``;
    a scope is an operation's path without its primitive's name."""
    scopes, runs = {}, 0
    for plane in data["device"]:
        programs = {m[4] for m in plane["modules"] if re.search(module, m[0])}
        runs += sum(1 for m in plane["modules"] if m[4] in programs)
        for scope, _, dur, program in plane["ops"]:
            if program in programs:
                scope = scope.rsplit("/", 1)[0]
                scopes[scope] = scopes.get(scope, 0.0) + dur
    return scopes, runs


def say(scopes: dict, runs: int, module: str) -> None:
    whole = sum(scopes.values()) or 1.0
    print(f"trace_scope_time: {module}: {runs} executions, "
          f"{whole / 1e6:.3f} ms in operations", file=sys.stderr)
    for scope, ns in sorted(scopes.items(), key=lambda kv: -kv[1])[:60]:
        print(f"  {ns / 1e6 / max(runs, 1):10.4f} ms/exec {100 * ns / whole:6.2f} %"
              f"  {scope or '(no scope)'}", file=sys.stderr)


def read(spec: dict, ctx: dict):
    data = trace_spans.of_run(ctx)
    if data is None:
        return None
    key = (id(data), spec["module"])
    if key not in _tables:
        _tables[key] = table(data, spec["module"])
        if _tables[key][0]:
            say(*_tables[key], spec["module"])
    scopes, runs = _tables[key]
    found = [ns for scope, ns in scopes.items()
             if re.search(spec["scope"], scope)]
    if not runs or not found:
        return None
    return sum(found) / 1e6 / runs
