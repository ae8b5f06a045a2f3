"""Set-up layer: what the first call of each of the cell's programs
spent beyond a second call, less its compiling: JAX's tracing, the
library's plans (``core/tiling.py``, the combinator optimizer) and
audits. Host clock."""


def read(run):
    return run["setup"]["trace_plan_s"]
