"""Pallas-kernel benchmark: interpret-mode correctness vs ref.py oracles +
XLA-path timing (CPU; TPU timings require real hardware — the dry-run
covers the structural side there).
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.flash.kernel import flash_attention
from repro.kernels.flash.ref import attention_ref
from repro.kernels.kvp.kernel import kvp
from repro.kernels.kvp.ref import kvp_ref
from repro.kernels.matern.kernel import matern52_gram
from repro.kernels.matern.ref import matern52_gram_ref


def _time(fn, *args, reps=20):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e6 * (time.perf_counter() - t0) / reps


def main(full=False):
    key = jax.random.PRNGKey(0)
    rows = []

    # matern gram
    n, d = (512, 20)
    k1, k2, k3 = jax.random.split(key, 3)
    x1 = jax.random.normal(k1, (n, d), jnp.float32)
    x2 = jax.random.normal(k2, (n, d), jnp.float32)
    ils = jnp.exp(jax.random.normal(k3, (d,), jnp.float32) * 0.3)
    amp = jnp.asarray(1.5, jnp.float32)
    err = float(jnp.max(jnp.abs(
        matern52_gram(x1, x2, ils, amp, interpret=True)
        - matern52_gram_ref(x1, x2, ils, amp))))
    us = _time(jax.jit(matern52_gram_ref), x1, x2, ils, amp)
    rows.append(("matern_gram_ref_xla", us, f"interp_err={err:.1e}"))

    # kvp
    al = jax.random.normal(k3, (n,), jnp.float32)
    err = float(jnp.max(jnp.abs(
        kvp(x1, x2, al, ils, amp, interpret=True)
        - kvp_ref(x1, x2, al, ils, amp))))
    us = _time(jax.jit(kvp_ref), x1, x2, al, ils, amp)
    rows.append(("kvp_ref_xla", us, f"interp_err={err:.1e}"))

    # flash attention
    s, h = (512, 64)
    q = jax.random.normal(k1, (s, h), jnp.float32)
    kk = jax.random.normal(k2, (s, h), jnp.float32)
    v = jax.random.normal(k3, (s, h), jnp.float32)
    err = float(jnp.max(jnp.abs(
        flash_attention(q, kk, v, causal=True, interpret=True)
        - attention_ref(q, kk, v, causal=True))))
    us = _time(jax.jit(lambda a, b, c: attention_ref(a, b, c, causal=True)),
               q, kk, v)
    rows.append(("flash_attn_ref_xla", us, f"interp_err={err:.1e}"))

    for name, us, derived in rows:
        print(f"kernel,{name},{us:.1f}us,{derived}")
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
