# virtual-path: flink_tpu/audit_fixture.py
# lint-kernel-fixture
#
# BAD: a jax.debug.print left over from a debugging session inside the
# scan body. It lowers to a debug_print primitive — a device->host
# round trip on EVERY scan iteration, invisible to source-level
# scanning once it hides in a helper, and exactly the serialization the
# megastep exists to avoid.


def lint_kernel_families():
    import jax
    import jax.numpy as jnp

    def kernel(x):
        def body(carry, _):
            jax.debug.print("carry sum {s}", s=carry.sum())
            return carry + 1.0, None

        y, _ = jax.lax.scan(body, x, None, length=4)
        return y

    return [{
        "name": "fixture.scan_with_debug_print",
        "fn": kernel,
        "args": (jax.ShapeDtypeStruct((8,), jnp.float32),),
    }]
