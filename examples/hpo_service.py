"""Multi-tenant HPO through the BO service: several model-zoo training
configurations share ONE fleet plane behind :class:`BOService`.

Each tenant is one architecture sweep — it owns a study, submits ask
requests through the service's asyncio facade, trains a reduced LM for a
few steps at the suggested (log lr, log weight decay), and tells the
final loss back.  Tenants run as independent coroutines at their own
pace (the big model trains slower, so its asks arrive sparser), while
the service task multiplexes everything onto the fleet under
deficit-round-robin fairness: the fast tenant's flood of requests cannot
starve the slow one, and all suggests still compile into the same <=3
fleet programs per (bucket, slots) shape.

Reduced scale by default so it runs on CPU in minutes:

    PYTHONPATH=src python examples/hpo_service.py
"""
import argparse
import asyncio

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp   # noqa: E402
import numpy as np        # noqa: E402

from repro.bo.sampler import FleetSampler         # noqa: E402
from repro.bo.space import BoxSpace               # noqa: E402
from repro.configs import get_config              # noqa: E402
from repro.core.mso import MsoOptions             # noqa: E402
from repro.data.synth import DataConfig, synth_batch   # noqa: E402
from repro.models import lm                       # noqa: E402
from repro.serve.bo_service import BOService, TenantConfig  # noqa: E402
from repro.train.optim import OptimConfig, init_opt_state   # noqa: E402
from repro.train.step import make_train_step      # noqa: E402

SPACE = BoxSpace(np.array([-5.0, -4.0]), np.array([-1.0, -0.5]))


def make_trial_fn(arch, width, layers, steps, batch, seq):
    cfg = get_config(arch).reduced().replace(
        dtype="float32", attn_chunk=32, d_model=width,
        n_layers=layers, d_ff=2 * width)
    dcfg = DataConfig(global_batch=batch, seq_len=seq, seed=0)

    def trial(x) -> float:
        log_lr, log_wd = float(x[0]), float(x[1])
        opt_cfg = OptimConfig(lr=10.0 ** log_lr,
                              weight_decay=10.0 ** log_wd,
                              warmup_steps=max(steps // 10, 1),
                              total_steps=steps)
        params = lm.init_params(jax.random.PRNGKey(0), cfg)
        opt_state = init_opt_state(params, opt_cfg)
        step = jax.jit(make_train_step(cfg, opt_cfg))
        loss = 20.0
        for i in range(steps):
            b = {k: jnp.asarray(v)
                 for k, v in synth_batch(cfg, dcfg, i).items()}
            params, opt_state, m = step(params, opt_state, b)
            loss = float(m["loss"])
            if not np.isfinite(loss):
                return 20.0
        return loss

    return trial


async def tenant_task(svc, name, study, trial_fn, n_trials):
    """One architecture sweep: ask → train → tell, at its own pace."""
    for _ in range(n_trials):
        t = await svc.ask(name, study)
        # training is synchronous compute; yield around it so the
        # service and the other tenants keep running between trials
        y = await asyncio.get_event_loop().run_in_executor(
            None, trial_fn, t.x)
        await svc.tell(name, study, t.trial_id, y)
        print(f"[{name}] trial {t.trial_id}: "
              f"log_lr={t.x[0]:+.2f} log_wd={t.x[1]:+.2f} "
              f"-> loss {y:.4f}", flush=True)
    best = svc.fs.samplers[study].best()
    print(f"[{name}] best: lr=10^{best.x[0]:.2f} "
          f"wd=10^{best.x[1]:.2f} loss={best.y:.4f}", flush=True)


async def serve(args):
    zoo = [
        # (tenant, arch, weight, width, layers, steps)
        ("small-fast", "llama3.2-3b", 1.0, 64, 2, args.steps),
        ("base", "llama3.2-3b", 2.0, args.width, args.layers, args.steps),
    ]
    fs = FleetSampler([SPACE] * len(zoo), seed=0, n_startup_trials=4,
                      n_restarts=6, pad_multiple=8, slots=4,
                      posterior_backend="xla", refit_interval=2,
                      mso_options=MsoOptions(maxiter=100, pgtol=1e-2))
    svc = BOService(fs, [
        TenantConfig(name, weight=w, studies=(i,))
        for i, (name, _a, w, *_rest) in enumerate(zoo)])
    server = asyncio.create_task(svc.run())
    await asyncio.gather(*[
        tenant_task(svc, name, i,
                    make_trial_fn(arch, width, layers, steps,
                                  args.batch, args.seq), args.trials)
        for i, (name, arch, _w, width, layers, steps) in enumerate(zoo)])
    svc.stop()
    await server
    snap = svc.stats_snapshot()
    print(f"\nservice: {snap['svc_completed']} asks served, "
          f"p99={snap['svc_p99_s']}, rung={snap['svc_rung']}, "
          f"fleet compiles={snap['n_fleet_compiles']}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--trials", type=int, default=8)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--width", type=int, default=128)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    args = ap.parse_args()
    asyncio.run(serve(args))


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
