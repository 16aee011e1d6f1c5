"""Long-tail RLlib algorithm families (round-5 additions): AlphaZero, Dreamer.

One of four files by family (test_rllib_longtail_*.py): a file is the
unit the tier-1 run balances across workers, so none may grow past
~150 s alone (ROADMAP.md, Tier-1 verify).

Learning thresholds follow the package's test strategy (short budgets,
clear pass bars — the analog of rllib's tuned_examples quick runs).
"""
import numpy as np

from _rl_fixtures import cluster  # noqa: F401


class TestAlphaZero:
    def _uniform_net(self):
        def fn(obs):
            n = len(obs)
            return (np.full((n, 9), 1.0 / 9, np.float32),
                    np.zeros(n, np.float32))
        return fn

    def test_mcts_finds_winning_move(self):
        """X to move with two in a row: search must pile visits on the
        completing square (pure search, uniform net)."""
        from ray_tpu.rllib.alpha_zero import TicTacToe, mcts_policy

        # X X . / O O . / . . .  -> X plays 2 to win
        board = np.array([[1, 1, 0, -1, -1, 0, 0, 0, 0]], np.int8)
        player = np.array([1], np.int8)
        pi = mcts_policy(TicTacToe, self._uniform_net(), board, player,
                         num_sims=64, c_puct=1.5, dirichlet_alpha=0.6,
                         dirichlet_eps=0.0,
                         rng=np.random.default_rng(0))
        assert pi[0].argmax() == 2, pi[0]

    def test_mcts_blocks_opponent_win(self):
        """O to move; X threatens at 2 — O must block (square 2)."""
        from ray_tpu.rllib.alpha_zero import TicTacToe, mcts_policy

        # X X . / O . . / . . .  O to move
        board = np.array([[1, 1, 0, -1, 0, 0, 0, 0, 0]], np.int8)
        player = np.array([-1], np.int8)
        pi = mcts_policy(TicTacToe, self._uniform_net(), board, player,
                         num_sims=128, c_puct=1.5, dirichlet_alpha=0.6,
                         dirichlet_eps=0.0,
                         rng=np.random.default_rng(0))
        assert pi[0].argmax() == 2, pi[0]

    def test_alphazero_beats_random(self, cluster):
        from ray_tpu.rllib import AlphaZeroConfig

        algo = AlphaZeroConfig(num_workers=2, games_per_worker=8,
                               num_sims=32, seed=0).build()
        try:
            last = None
            ok = False
            for i in range(20):
                r = algo.train()
                if "loss" in r:
                    last = r
                if i % 4 == 3:
                    ev = algo.evaluate_vs_random(num_games=16)
                    if ev["non_loss_rate"] >= 0.95:
                        ok = True
                        break
            assert ok, ev
            # the net trained (gated on buffer fill) with finite losses
            assert last is not None and np.isfinite(last["loss"]), last
            ckpt = algo.save()
            algo.restore(ckpt)
        finally:
            algo.stop()


class TestDreamer:
    def test_np_jax_gru_parity(self):
        """The worker's numpy GRU/MLP must match the learner's jax
        cells — the rollout policy IS the world model's RSSM."""
        import jax
        import jax.numpy as jnp

        from ray_tpu.rllib.dreamer import (_np_gru, _np_mlp2,
                                           init_dreamer_params)

        p = init_dreamer_params(jax.random.PRNGKey(0), 4, 2, deter=16,
                                n_cat=4, n_cls=4, hidden=8)
        p_np = {k: np.asarray(v) for k, v in p.items()}
        rng = np.random.default_rng(1)
        x = rng.normal(size=(3, 16 + 2)).astype(np.float32)
        h = rng.normal(size=(3, 16)).astype(np.float32)

        def jax_gru(p, x, h):
            zg = x @ p["gru_wx"] + h @ p["gru_wh"] + p["gru_wx_b"]
            G = h.shape[1]
            r = jax.nn.sigmoid(zg[:, :G])
            u = jax.nn.sigmoid(zg[:, G:2 * G] - 1.0)
            cand = jnp.tanh(zg[:, 2 * G:]
                            + (r - 1.0) * (h @ p["gru_wh"][:, 2 * G:]))
            return u * h + (1.0 - u) * cand

        np.testing.assert_allclose(
            _np_gru(p_np, x, h), np.asarray(jax_gru(p, x, h)), atol=1e-5)
        obs = rng.normal(size=(3, 4)).astype(np.float32)
        emb_np = _np_mlp2(p_np, "enc", obs, act_last=True)
        emb_j = jax.nn.relu(
            jax.nn.relu(obs @ p["enc_w0"] + p["enc_w0_b"])
            @ p["enc_w1"] + p["enc_w1_b"])
        np.testing.assert_allclose(emb_np, np.asarray(emb_j), atol=1e-5)

    def test_dreamer_learns_cartpole_in_imagination(self, cluster):
        """The model-based family: world model + actor trained purely
        in imagination must lift real returns well above random (~20)."""
        from ray_tpu.rllib import DreamerConfig

        algo = DreamerConfig(num_rollout_workers=1,
                             num_envs_per_worker=8,
                             rollout_fragment_length=64, seq_len=16,
                             learning_starts=50,
                             num_updates_per_iter=4, seed=0).build()
        try:
            best = 0.0
            for _ in range(150):
                r = algo.train()
                m = r["episode_reward_mean"]
                if np.isfinite(m):
                    best = max(best, m)
                if best >= 100:
                    break
            assert best >= 100, best
        finally:
            algo.stop()

    def test_dreamer_checkpoint_roundtrip(self, cluster):
        from ray_tpu.rllib import DreamerConfig

        cfg = dict(num_rollout_workers=1, num_envs_per_worker=4,
                   rollout_fragment_length=16, seq_len=8,
                   learning_starts=4, num_updates_per_iter=1,
                   train_batch_size=4, deter=32, hidden=32)
        a = DreamerConfig(seed=1, **cfg).build()
        try:
            a.train()
            a.train()
            ckpt = a.save()
            b = DreamerConfig(seed=2, **cfg).build()
            try:
                b.restore(ckpt)
                import jax

                wa = jax.device_get(a.learner.wm)
                wb = jax.device_get(b.learner.wm)
                for k in wa:
                    np.testing.assert_allclose(wa[k], wb[k], err_msg=k)
                assert len(b.buffer) == len(a.buffer)
            finally:
                b.stop()
        finally:
            a.stop()
