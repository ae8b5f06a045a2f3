"""The four-chip reshard through the library's sharded entry point:
``distributed.make_distributed_bmmc(b, s, mesh)``, one jitted
``shard_map`` program per matrix over a binary mesh of the cell's
chips. Its local rounds run the class-dispatched Pallas kernels, its
shard moves ``ppermute`` and ``all_to_all``. Inputs are made with the
leading axis split over the mesh, as the program takes them."""


def build(cfg, mix, devices):
    from jax.sharding import NamedSharding
    from repro.core import distributed
    from repro.core.bmmc import Bmmc
    s = len(devices).bit_length() - 1
    mesh = distributed.binary_mesh(s, devices)
    calls = [(op["name"], distributed.make_distributed_bmmc(
        Bmmc(tuple(op["rows"]), op["c"]), s, mesh)) for op in mix["ops"]]
    return calls, NamedSharding(mesh, distributed.shard_spec(s))
