"""Host->device transfer bandwidth curve on the chip."""

import sys
import time

import numpy as np


def main():
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    for mb in (1, 4, 16, 64):
        a = rng.integers(0, 255, size=mb * 1024 * 1024, dtype=np.uint8)
        d = jax.device_put(a)
        jax.block_until_ready(d)
        t0 = time.perf_counter()
        iters = 3
        for _ in range(iters):
            d = jax.device_put(a)
            jax.block_until_ready(d)
        dt = (time.perf_counter() - t0) / iters
        print(f"{mb:3d} MB: {dt*1e3:8.1f} ms  ->  {mb/dt:7.1f} MB/s")

    # async overlap: dispatch N device_puts without blocking, then sync once
    a = rng.integers(0, 255, size=4 * 1024 * 1024, dtype=np.uint8)
    t0 = time.perf_counter()
    ds = [jax.device_put(a) for _ in range(8)]
    jax.block_until_ready(ds)
    dt = time.perf_counter() - t0
    print(f"8x 4MB async: {dt*1e3:8.1f} ms -> {32/dt:7.1f} MB/s aggregate")

    # d2h for comparison
    t0 = time.perf_counter()
    _ = np.asarray(ds[0])
    dt = time.perf_counter() - t0
    print(f"d2h 4MB: {dt*1e3:8.1f} ms -> {4/dt:7.1f} MB/s")


if __name__ == "__main__":
    main()
