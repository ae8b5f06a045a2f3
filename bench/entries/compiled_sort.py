"""The compiled sort's forward: ``CompiledExpr.__call__`` of
``compiled_sort(n, engine="pallas")``, called as a user calls it (no
outer jit): whole-program executable, fused megakernels and sweeps."""


def build(cfg, mix, devices):
    from repro.combinators.sort import compiled_sort
    return [(mix["ops"][0]["name"], compiled_sort(cfg["n"], engine="pallas"))], None
