"""Batched serving with continuous batching on a reduced llama config.

    PYTHONPATH=src python examples/serve_batched.py
"""
import jax
import numpy as np

from repro.configs import get_config
from repro.models import lm
from repro.serve.engine import Request, ServeEngine


def main():
    cfg = get_config("llama3.2-3b").reduced().replace(dtype="float32",
                                                      attn_chunk=16)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(params, cfg, slots=4, max_len=128)

    rng = np.random.default_rng(0)
    for uid in range(10):
        eng.submit(Request(
            uid=uid,
            prompt=rng.integers(0, cfg.vocab_size,
                                4 + uid % 5).astype(np.int32),
            max_new_tokens=12))
    done = eng.run_until_drained()
    print(f"served {len(done)} requests / {eng.stats['tokens']} tokens "
          f"in {eng.stats['steps']} steps "
          f"({eng.stats['wall']:.2f}s device time)")
    for r in done[:3]:
        print(f"  uid={r.uid}: {r.out_tokens}")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
