"""Device->host transfer characterization: fetch latency by size on the chip."""

import time

import numpy as np


def main():
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(0)

    def t_once(f):
        t0 = time.perf_counter()
        out = f()
        return (time.perf_counter() - t0) * 1e3, out

    for kb in (4, 64, 1024, 4096):
        n = kb * 1024
        d = jax.device_put(rng.integers(0, 255, size=n, dtype=np.uint8))
        jax.block_until_ready(d)
        ms1, _ = t_once(lambda: np.asarray(d))
        ms2, _ = t_once(lambda: jax.device_get(d))
        ms3, _ = t_once(lambda: np.asarray(d))
        print(f"{kb:5d} KB: np.asarray {ms1:9.1f} ms | device_get "
              f"{ms2:9.1f} ms | again {ms3:9.1f} ms "
              f"-> {kb/1024/ (ms3/1e3):6.1f} MB/s")

    # is it the transfer or the sync? time a tiny readback after big compute
    big = jax.device_put(rng.random((4096, 4096)).astype(np.float32))

    @jax.jit
    def work(x):
        for _ in range(8):
            x = x @ x
        return x.sum()

    s = work(big)
    jax.block_until_ready(s)
    ms, _ = t_once(lambda: float(work(big)))
    print(f"scalar readback after matmul chain: {ms:9.1f} ms")

    # jit output already on device; read slices of growing size
    d = jax.device_put(rng.integers(0, 255, size=1 << 24, dtype=np.uint8))
    jax.block_until_ready(d)
    for n in (1 << 10, 1 << 16, 1 << 20, 1 << 22):
        sl = d[:n]
        jax.block_until_ready(sl)
        ms, _ = t_once(lambda: np.asarray(sl))
        print(f"slice {n:>9,} B readback: {ms:9.1f} ms "
              f"-> {n/1e6/(ms/1e3):7.1f} MB/s")


if __name__ == "__main__":
    main()
