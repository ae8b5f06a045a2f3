"""The paper's suite through the library's public entry point:
``ops.make_bmmc_permute(b)``, one jitted function per matrix, which runs
class dispatch, the ``core/tiling.py`` plans and the Pallas kernels."""


def build(cfg, mix, devices):
    from repro.core.bmmc import Bmmc
    from repro.kernels import ops
    calls = [(op["name"], ops.make_bmmc_permute(Bmmc(tuple(op["rows"]), op["c"])))
             for op in mix["ops"]]
    return calls, None
