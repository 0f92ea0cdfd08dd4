"""End-to-end driver: BO (control plane, D-BE inside) tunes the learning
rate + weight decay of an LM training run (data plane).

Reduced scale by default so it runs on CPU in minutes; pass --arch/--steps
/--width to scale up (the same driver shape runs a ~100M model for a few
hundred steps on real hardware: --width 768 --layers 12 --steps 300).

    PYTHONPATH=src python examples/hpo_train.py
"""
import argparse

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp   # noqa: E402
import numpy as np        # noqa: E402

from repro.bo.sampler import GPSampler            # noqa: E402
from repro.bo.space import BoxSpace               # noqa: E402
from repro.configs import get_config              # noqa: E402
from repro.core.mso import MsoOptions             # noqa: E402
from repro.data.synth import DataConfig, synth_batch   # noqa: E402
from repro.models import lm                       # noqa: E402
from repro.train.optim import OptimConfig, init_opt_state  # noqa: E402
from repro.train.step import make_train_step      # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b")
    ap.add_argument("--trials", type=int, default=12)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--width", type=int, default=128)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    args = ap.parse_args()

    cfg = get_config(args.arch).reduced().replace(
        dtype="float32", attn_chunk=32, d_model=args.width,
        n_layers=args.layers, d_ff=2 * args.width)
    dcfg = DataConfig(global_batch=args.batch, seq_len=args.seq, seed=0)

    def trial(x) -> float:
        log_lr, log_wd = float(x[0]), float(x[1])
        opt_cfg = OptimConfig(lr=10.0 ** log_lr,
                              weight_decay=10.0 ** log_wd,
                              warmup_steps=max(args.steps // 10, 1),
                              total_steps=args.steps)
        params = lm.init_params(jax.random.PRNGKey(0), cfg)
        opt_state = init_opt_state(params, opt_cfg)
        step = jax.jit(make_train_step(cfg, opt_cfg))
        loss = 20.0
        for i in range(args.steps):
            batch = {k: jnp.asarray(v)
                     for k, v in synth_batch(cfg, dcfg, i).items()}
            params, opt_state, m = step(params, opt_state, batch)
            loss = float(m["loss"])
            if not np.isfinite(loss):
                return 20.0
        return loss

    space = BoxSpace(np.array([-5.0, -4.0]), np.array([-1.0, -0.5]))
    sampler = GPSampler(space, strategy="dbe", seed=0, n_startup_trials=5,
                        n_restarts=6,
                        mso_options=MsoOptions(maxiter=100, pgtol=1e-2))
    for i in range(args.trials):
        t = sampler.ask()
        y = trial(t.x)
        sampler.tell(t.trial_id, y)
        print(f"trial {t.trial_id}: log_lr={t.x[0]:+.2f} "
              f"log_wd={t.x[1]:+.2f} -> final loss {y:.4f}", flush=True)
    best = sampler.best()
    print(f"\nbest: lr=10^{best.x[0]:.2f} wd=10^{best.x[1]:.2f} "
          f"loss={best.y:.4f}")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
