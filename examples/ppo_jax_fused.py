"""Fused device-resident PPO (the Podracer/"Anakin" layout): env,
rollout, GAE, and SGD compile into ONE XLA program per dispatch — the
pipeline that runs the pixels benchmark at ~160k env-steps/s on a
single v5e chip (vs ~100-500/s for any host-rollout design over a slow
host<->device link; a reading of round 5, before PR 21: in git,
`git show a61f7ab:docs/PERF_NOTES.md`, not measured on today's code).

Usage:
    python examples/ppo_jax_fused.py                   # CartPole
    python examples/ppo_jax_fused.py --env BreakoutShaped-v0 --hidden 512
"""
import argparse
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--env", default="CartPole-v1",
                    choices=["CartPole-v1", "BreakoutShaped-v0"])
    ap.add_argument("--num-envs", type=int, default=64)
    ap.add_argument("--rollout-len", type=int, default=64)
    ap.add_argument("--iters-per-step", type=int, default=4)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--epochs", type=int, default=4)
    args = ap.parse_args()

    from ray_tpu.rllib import PPOJaxConfig

    algo = PPOJaxConfig(
        env=args.env, num_envs=args.num_envs,
        rollout_len=args.rollout_len, iters_per_step=args.iters_per_step,
        sgd_minibatch_size=min(1024, args.num_envs * args.rollout_len),
        num_sgd_epochs=args.epochs,
        hidden=(args.hidden,) if args.env.startswith("Breakout")
        else (args.hidden, args.hidden)).build()
    t0 = time.time()
    for i in range(args.steps):
        r = algo.train()
        if i % 5 == 0 or i == args.steps - 1:
            print(f"[{i:3d}] reward={r['episode_reward_mean']:8.2f} "
                  f"steps/s={r['env_steps_per_sec']:>9.0f} "
                  f"total={r['timesteps_total']}")
    print(f"done: {r['timesteps_total']} env steps in "
          f"{time.time() - t0:.1f}s")


if __name__ == "__main__":
    main()
