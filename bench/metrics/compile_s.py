"""Set-up layer: the union of JAX's backend compiles during set-up,
persistent-cache reads included, from JAX's own monitoring spans.
Served from the cache after a checkout's first run. Host clock."""


def read(run):
    return run["setup"]["compile_s"]
