import time
import numpy as np
import jax, jax.numpy as jnp

N = 176_000_000  # 704MB f32
x = jnp.ones((N,), jnp.float32)

@jax.jit
def f(x):
    return (x * 1.000001 + 1e-9).sum()

float(f(x))
for reps in (10,):
    t0 = time.time()
    s = 0.0
    for _ in range(reps):
        s = f(x)
    float(s)
    dt = (time.time() - t0) / reps
    print(f"read 704MB + reduce: {dt*1e3:.1f} ms -> {N*4/dt/1e9:.0f} GB/s")

# write test: y = x*2 (read+write 1.4GB)
@jax.jit
def g(x):
    return x * 2.0

y = g(x); float(y[0])
t0 = time.time()
for _ in range(10):
    y = g(y)
float(y[0])
dt = (time.time() - t0) / 10
print(f"read+write 704MB each: {dt*1e3:.1f} ms -> {2*N*4/dt/1e9:.0f} GB/s")
