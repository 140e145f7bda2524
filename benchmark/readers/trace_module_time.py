"""Device milliseconds per execution of the XLA modules whose jit name
matches ``module`` (a regular expression), from the profiler trace."""

import re


def modules(spec: dict, ctx: dict):
    """(seconds, executions) of the matching modules in the trace."""
    if ctx["trace"] is None:
        return 0.0, 0.0
    found = [v for k, v in ctx["trace"]["modules"].items()
             if re.search(spec["module"], k)]
    return (sum(v["seconds"] for v in found),
            sum(v["executions"] for v in found))


def read(spec: dict, ctx: dict):
    seconds, runs = modules(spec, ctx)
    return 1e3 * seconds / runs if runs else None
