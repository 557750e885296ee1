"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch internlm2-1.8b \
        --smoke --steps 50 --batch 8 --seq 64

``--smoke`` trains the reduced config on host devices (the CPU-scale
end-to-end driver); without it the full config is used (real TPU pods).
``--devices N`` requests N host devices (set before jax init).
``--inject-fault S`` raises a RestartSignal at step S to exercise the
checkpoint-restore path from the CLI.
"""
import argparse
import os


def _parse():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--devices", type=int, default=0)
    ap.add_argument("--mesh", default="", help="e.g. 2x2 (data x model)")
    ap.add_argument("--summa", default="",
                    help="distributed-SUMMA self-check grid, e.g. 2x2 "
                         "(defaults to the arch's summa_grid)")
    ap.add_argument("--formats", default="",
                    help="override the arch's mixed-precision format set, "
                         "e.g. fp8_e4m3+bf16+fp32 or the short form "
                         "q:s:d (aliases: d=fp32 s=bf16 q=fp8_e4m3 "
                         "int8=int8_pt int4=int4_pt)")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--inject-fault", type=int, default=-1)
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args()


def main():
    args = _parse()
    if args.devices:
        # append, don't overwrite: the user's other XLA flags must survive
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + " "
            f"--xla_force_host_platform_device_count={args.devices}").strip()
    import jax

    from repro import config
    from repro.configs import get, load_all, reduced
    from repro.optim import adamw
    from repro.runtime.fault import RestartSignal
    from repro.train.trainer import TrainerConfig, train

    config.use_compile_cache()
    load_all()
    cfg = get(args.arch)
    if args.smoke:
        cfg = reduced(cfg, tp=2)
    if args.formats:
        import dataclasses

        from repro.core.formats import FormatSet
        cfg = dataclasses.replace(
            cfg, mp_formats=FormatSet.parse(args.formats).key())

    grid = (tuple(int(v) for v in args.summa.lower().split("x"))
            if args.summa else cfg.summa_grid)
    if grid:
        # validate the distributed SUMMA path (and warm its plan key) at
        # this config's tile/policy/format set before training starts
        from repro.core.summa import config_selfcheck
        rep = config_selfcheck(cfg, grid)
        print(f"SUMMA self-check {rep['grid']} [{rep['formats']}]: "
              f"local path {rep['local_path']} ({rep['plan_source']}), "
              f"rel err {rep['rel_err']:.2e}, "
              f"wire {rep['wire_bytes_per_elem']:.2f} B/elem")

    ocfg = adamw.AdamWConfig(lr_peak=args.lr, warmup_steps=min(
        20, args.steps // 5), total_steps=args.steps)

    injector = None
    if args.inject_fault >= 0:
        fired = {"done": False}

        def injector(step, fired=fired):
            if step == args.inject_fault and not fired["done"]:
                fired["done"] = True
                raise RestartSignal("CLI-injected fault")

    tcfg = TrainerConfig(
        steps=args.steps, seq_len=args.seq, global_batch=args.batch,
        microbatches=args.microbatches, ckpt_dir=args.ckpt_dir,
        ckpt_every=max(10, args.steps // 5), log_every=5, seed=args.seed,
        heartbeat_path=os.path.join(args.ckpt_dir, "heartbeat.json"),
        fault_injector=injector)

    params = opt = None
    start = 0
    if args.resume:
        from repro.checkpoint import ckpt as CK
        from repro.models import transformer as T
        latest = CK.AsyncCheckpointer(args.ckpt_dir).latest()
        if latest:
            params = T.init_model(jax.random.PRNGKey(args.seed), cfg)
            opt = adamw.init(params, ocfg)
            restored, man = CK.restore(latest,
                                       {"params": params, "opt": opt})
            params, opt = restored["params"], restored["opt"]
            start = man["step"]
            print(f"resumed from {latest} at step {start}")

    params, opt, hist = train(cfg, ocfg, tcfg, params=params,
                              opt_state=opt, start_step=start)
    losses = [h["loss"] for h in hist]
    print(f"done: {len(hist)} steps, loss {losses[0]:.4f} → "
          f"{losses[-1]:.4f}")


if __name__ == "__main__":
    main()
