"""Every sharded kernel builds on the installed jax's own ``jax.shard_map``
(no version shim), and that symbol runs on the 8-device CPU mesh."""

import pathlib

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

# module -> number of `from jax import shard_map` imports it holds
_SITES = {
    "runtime/step.py": 1,
    "runtime/dcn.py": 4,
    "cep/accel.py": 1,
    "parallel/broadcast.py": 1,
}


def test_sharded_kernels_use_jax_shard_map_on_the_8_device_mesh():
    import flink_tpu.parallel.broadcast as broadcast
    import flink_tpu.runtime.step as step

    assert step.shard_map is jax.shard_map
    assert broadcast.shard_map is jax.shard_map
    root = pathlib.Path(__file__).resolve().parent.parent / "flink_tpu"
    for rel, n in _SITES.items():
        assert (root / rel).read_text().count("from jax import shard_map") == n
    assert not any("flink_tpu.core.compat" in p.read_text()
                   for p in root.rglob("*.py"))

    assert len(jax.devices()) == 8
    mesh = Mesh(np.asarray(jax.devices()), ("shards",))
    f = jax.shard_map(
        lambda x: x + jax.lax.axis_index("shards"), mesh=mesh,
        in_specs=(P("shards"),), out_specs=P("shards"), check_vma=False,
    )
    out = np.asarray(f(jnp.zeros((8, 4), jnp.int32)))
    np.testing.assert_array_equal(out, np.repeat(np.arange(8), 4).reshape(8, 4))
