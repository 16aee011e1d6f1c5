"""AlphaZero — self-play MCTS planning with a learned policy/value net
(Silver et al. 2017).

ref: rllib/algorithms/alpha_zero/alpha_zero.py (+ mcts.py: PUCT
selection, Dirichlet root noise, visit-count policy targets;
ranked_rewards omitted — two-player zero-sum games need no reward
ranking). The reference couples MCTS to single gym envs per worker;
here self-play actors run a BATCHED MCTS: one tree per live game, but
every simulation step evaluates all games' leaves through the network
in one batch — the vectorized-env discipline the rest of this rllib
uses, applied to tree search.

Game contract (two-player, zero-sum, turn-based) is a tiny numpy
protocol (`TicTacToe` ships as the test surface): canonical boards —
the network always sees the position from the player-to-move's
perspective, so one net plays both sides.

Learner: visit-count cross-entropy + outcome MSE, all minibatches in
one jitted lax.scan dispatch (the learner rule of this package).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Tuple

import numpy as np

import ray_tpu

from .rollout_worker import worker_opts


# ---------------------------------------------------------------------------
# game protocol + TicTacToe
# ---------------------------------------------------------------------------


class TicTacToe:
    """Vector-friendly two-player game: boards are [n, board_size] int8
    arrays with stones stored absolutely (+1 = X, -1 = O).

    Static/class-method protocol so MCTS/self-play need no instances —
    custom games implement exactly these names (A, OBS_DIM class attrs
    plus):
      initial(n) -> (boards [n, board_size], players [n])
      legal(boards) -> [n, A] bool
      play(boards, players, actions) -> (boards, players)  # next mover
      terminal_value(boards, players) -> [n] float in {-1, 0, +1} from
        the perspective of the PLAYER TO MOVE (players[i]): -1 means
        the mover has already lost (the usual case — the opponent just
        completed a line); nan while the game is live
      canonical(boards, players) -> [n, OBS_DIM] float32 net input from
        the player-to-move's perspective
    """

    A = 9
    OBS_DIM = 18  # own stones one-hot + opponent stones one-hot

    _WINS = np.array([[0, 1, 2], [3, 4, 5], [6, 7, 8],
                      [0, 3, 6], [1, 4, 7], [2, 5, 8],
                      [0, 4, 8], [2, 4, 6]])

    @staticmethod
    def initial(n: int) -> Tuple[np.ndarray, np.ndarray]:
        return (np.zeros((n, 9), np.int8), np.ones(n, np.int8))

    @staticmethod
    def legal(boards: np.ndarray) -> np.ndarray:
        return boards == 0

    @staticmethod
    def play(boards: np.ndarray, players: np.ndarray,
             actions: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        out = boards.copy()
        out[np.arange(len(out)), actions] = players
        return out, (-players).astype(np.int8)

    @classmethod
    def winner(cls, boards: np.ndarray) -> np.ndarray:
        """[n] in {+1, -1, 0=none-yet-or-draw}."""
        lines = boards[:, cls._WINS]          # [n, 8, 3]
        sums = lines.sum(axis=2)
        w = np.zeros(len(boards), np.int8)
        w[(sums == 3).any(axis=1)] = 1
        w[(sums == -3).any(axis=1)] = -1
        return w

    @classmethod
    def terminal_value(cls, boards: np.ndarray, players: np.ndarray
                       ) -> np.ndarray:
        """Value from the PLAYER-TO-MOVE's perspective: +1 win, -1
        loss, 0 draw; nan while the game is live."""
        w = cls.winner(boards)
        full = (boards != 0).all(axis=1)
        v = np.full(len(boards), np.nan, np.float32)
        done = (w != 0) | full
        v[done] = 0.0
        # if a line exists it belongs to the player who just moved —
        # the player to move has LOST
        v[w == players] = 1.0    # (cannot happen in legal play; safety)
        v[(w != 0) & (w != players)] = -1.0
        return v

    @staticmethod
    def canonical(boards: np.ndarray, players: np.ndarray) -> np.ndarray:
        mine = (boards == players[:, None]).astype(np.float32)
        theirs = (boards == -players[:, None]).astype(np.float32)
        return np.concatenate([mine, theirs], axis=1)


_GAMES: Dict[str, Any] = {"TicTacToe-v0": TicTacToe}


def register_game(name: str, game) -> None:
    _GAMES[name] = game


# ---------------------------------------------------------------------------
# batched MCTS (numpy, one tree per game, batched leaf evaluation)
# ---------------------------------------------------------------------------


class _Tree:
    """One game's search tree in flat arrays (ref: mcts.py Node — here
    arrays-of-nodes instead of node objects)."""

    def __init__(self, max_nodes: int, A: int, board_size: int):
        self.N = np.zeros((max_nodes, A), np.float32)   # visit counts
        self.W = np.zeros((max_nodes, A), np.float32)   # total value
        self.P = np.zeros((max_nodes, A), np.float32)   # priors
        self.children = np.full((max_nodes, A), -1, np.int32)
        self.boards = np.zeros((max_nodes, board_size), np.int8)
        self.players = np.zeros(max_nodes, np.int8)
        self.legal = np.zeros((max_nodes, A), bool)
        self.terminal_v = np.full(max_nodes, np.nan, np.float32)
        self.size = 0

    def add(self, board, player, legal, term_v) -> int:
        i = self.size
        self.size += 1
        self.boards[i], self.players[i] = board, player
        self.legal[i] = legal
        self.terminal_v[i] = term_v
        return i


def mcts_policy(game, forward_fn, boards: np.ndarray,
                players: np.ndarray, *, num_sims: int, c_puct: float,
                dirichlet_alpha: float, dirichlet_eps: float,
                rng: np.random.Generator) -> np.ndarray:
    """Run PUCT search for every live game; returns visit-count
    distributions [n, A] (ref: mcts.py compute_action + the AlphaZero
    paper's search)."""
    n, A = len(boards), game.A
    board_size = boards.shape[1]
    max_nodes = num_sims + 2
    trees = [_Tree(max_nodes, A, board_size) for _ in range(n)]
    # root eval (batched) + Dirichlet noise
    probs, _ = forward_fn(game.canonical(boards, players))
    for i, t in enumerate(trees):
        legal = game.legal(boards[i:i + 1])[0]
        term = game.terminal_value(boards[i:i + 1], players[i:i + 1])[0]
        t.add(boards[i], players[i], legal, term)
        p = probs[i] * legal
        p = p / max(p.sum(), 1e-9)
        noise = rng.dirichlet([dirichlet_alpha] * int(legal.sum()))
        p[legal] = (1 - dirichlet_eps) * p[legal] + dirichlet_eps * noise
        t.P[0] = p

    for _ in range(num_sims):
        # phase 1: descend every tree to a leaf
        paths: List[List[Tuple[int, int]]] = []
        leaf_boards = np.zeros((n, board_size), np.int8)
        leaf_players = np.zeros(n, np.int8)
        leaf_node = np.zeros(n, np.int32)
        needs_eval = np.zeros(n, bool)
        for i, t in enumerate(trees):
            node = 0
            path: List[Tuple[int, int]] = []
            while True:
                if not np.isnan(t.terminal_v[node]):
                    break  # terminal leaf
                sqrt_n = np.sqrt(max(1.0, t.N[node].sum()))
                q = np.where(t.N[node] > 0,
                             t.W[node] / np.maximum(t.N[node], 1e-9),
                             0.0)
                u = c_puct * t.P[node] * sqrt_n / (1.0 + t.N[node])
                score = np.where(t.legal[node], q + u, -np.inf)
                a = int(score.argmax())
                child = t.children[node, a]
                if child < 0:
                    # expand: play the move, add the child node
                    nb, npl = game.play(t.boards[node:node + 1],
                                        t.players[node:node + 1],
                                        np.array([a]))
                    term = game.terminal_value(nb, npl)[0]
                    legal = game.legal(nb)[0]
                    child = t.add(nb[0], npl[0], legal, term)
                    t.children[node, a] = child
                    path.append((node, a))
                    node = child
                    break
                path.append((node, a))
                node = child
            paths.append(path)
            leaf_node[i] = node
            if np.isnan(trees[i].terminal_v[node]):
                needs_eval[i] = True
                leaf_boards[i] = trees[i].boards[node]
                leaf_players[i] = trees[i].players[node]

        # phase 2: ONE batched net call for all non-terminal leaves
        if needs_eval.any():
            idx = np.nonzero(needs_eval)[0]
            probs, values = forward_fn(
                game.canonical(leaf_boards[idx], leaf_players[idx]))
            for j, i in enumerate(idx):
                t = trees[i]
                node = leaf_node[i]
                p = probs[j] * t.legal[node]
                t.P[node] = p / max(p.sum(), 1e-9)

        # phase 3: backup
        for i, t in enumerate(trees):
            node = leaf_node[i]
            if not np.isnan(t.terminal_v[node]):
                v = float(t.terminal_v[node])
            else:
                # rank of game i among the batch-evaluated leaves
                v = float(values[np.count_nonzero(needs_eval[:i])])
            # v is from the LEAF's player-to-move perspective; flip as
            # we walk back up (alternating turns)
            for (pn, pa) in reversed(paths[i]):
                v = -v  # parent is the other player
                t.N[pn, pa] += 1.0
                t.W[pn, pa] += v

    visits = np.stack([t.N[0] for t in trees])
    return visits / np.maximum(visits.sum(axis=1, keepdims=True), 1e-9)


# ---------------------------------------------------------------------------
# self-play worker / learner / driver
# ---------------------------------------------------------------------------


class AlphaZeroSelfPlayWorker:
    """Plays batched self-play games with MCTS; emits
    (canonical_obs, visit_policy, outcome) training triples."""

    def __init__(self, game_name: str, num_games: int, num_sims: int,
                 c_puct: float, temperature_moves: int,
                 dirichlet_alpha: float, dirichlet_eps: float,
                 seed: int = 0):
        from .np_policy import forward_np

        self.game = _GAMES[game_name]
        self.n = num_games
        self.num_sims = num_sims
        self.c_puct = c_puct
        self.temp_moves = temperature_moves
        self.dir_alpha = dirichlet_alpha
        self.dir_eps = dirichlet_eps
        self._rng = np.random.default_rng(seed)
        self._forward_np = forward_np

    def _forward(self, params):
        def fn(obs):
            logits, values = self._forward_np(params, obs)
            ex = np.exp(logits - logits.max(axis=1, keepdims=True))
            return ex / ex.sum(axis=1, keepdims=True), np.tanh(values)
        return fn

    def self_play(self, params: Dict) -> Dict[str, np.ndarray]:
        from .np_policy import ensure_numpy

        game = self.game
        fwd = self._forward(ensure_numpy(params))
        boards, players = game.initial(self.n)
        live = np.ones(self.n, bool)
        # per-game trajectory of (obs, pi, player)
        obs_tr: List[List[np.ndarray]] = [[] for _ in range(self.n)]
        pi_tr: List[List[np.ndarray]] = [[] for _ in range(self.n)]
        pl_tr: List[List[int]] = [[] for _ in range(self.n)]
        outcome = np.zeros(self.n, np.float32)  # from X's perspective
        move = 0
        while live.any():
            idx = np.nonzero(live)[0]
            pis = mcts_policy(
                game, fwd, boards[idx], players[idx],
                num_sims=self.num_sims, c_puct=self.c_puct,
                dirichlet_alpha=self.dir_alpha,
                dirichlet_eps=self.dir_eps, rng=self._rng)
            cano = game.canonical(boards[idx], players[idx])
            acts = np.zeros(len(idx), np.int64)
            for j, i in enumerate(idx):
                obs_tr[i].append(cano[j])
                pi_tr[i].append(pis[j])
                pl_tr[i].append(int(players[i]))
                if move < self.temp_moves:
                    acts[j] = self._rng.choice(game.A, p=pis[j])
                else:
                    acts[j] = int(pis[j].argmax())
            nb, npl = game.play(boards[idx], players[idx], acts)
            boards[idx], players[idx] = nb, npl
            term = game.terminal_value(nb, npl)
            for j, i in enumerate(idx):
                if not np.isnan(term[j]):
                    live[i] = False
                    # term is from the new player-to-move's perspective;
                    # convert to X's: player-to-move is npl[j]
                    outcome[i] = term[j] * npl[j]
            move += 1
        obs, pis, zs = [], [], []
        for i in range(self.n):
            for o, p, pl in zip(obs_tr[i], pi_tr[i], pl_tr[i]):
                obs.append(o)
                pis.append(p)
                zs.append(outcome[i] * pl)  # outcome from mover's view
        return {"obs": np.asarray(obs, np.float32),
                "pi": np.asarray(pis, np.float32),
                "z": np.asarray(zs, np.float32),
                "games": np.float32(self.n),
                "x_score": np.float32(outcome.mean())}

    def evaluate_vs_random(self, params: Dict, num_games: int,
                           seed: int = 0) -> Dict[str, float]:
        """Greedy 1-sim... full-MCTS agent as X vs uniform-random O and
        vice versa; returns non-loss rate (ref: alpha_zero examples'
        eval against random play)."""
        from .np_policy import ensure_numpy

        game = self.game
        fwd = self._forward(ensure_numpy(params))
        rng = np.random.default_rng(seed)
        results = []
        for agent_is_x in (True, False):
            boards, players = game.initial(num_games)
            live = np.ones(num_games, bool)
            outcome = np.zeros(num_games, np.float32)
            while live.any():
                idx = np.nonzero(live)[0]
                agent_turn = (players[idx] == 1) == agent_is_x
                acts = np.zeros(len(idx), np.int64)
                if agent_turn.any():
                    ai = idx[agent_turn]
                    pis = mcts_policy(
                        game, fwd, boards[ai], players[ai],
                        num_sims=self.num_sims, c_puct=self.c_puct,
                        dirichlet_alpha=self.dir_alpha,
                        dirichlet_eps=0.0, rng=rng)
                    acts[agent_turn] = pis.argmax(axis=1)
                if (~agent_turn).any():
                    ri = idx[~agent_turn]
                    legal = game.legal(boards[ri])
                    for j, gi in enumerate(ri):
                        choices = np.nonzero(legal[j])[0]
                        acts[np.nonzero(~agent_turn)[0][j]] = \
                            rng.choice(choices)
                nb, npl = game.play(boards[idx], players[idx], acts)
                boards[idx], players[idx] = nb, npl
                term = game.terminal_value(nb, npl)
                for j, i in enumerate(idx):
                    if not np.isnan(term[j]):
                        live[i] = False
                        outcome[i] = term[j] * npl[j]  # X's perspective
            agent_score = outcome if agent_is_x else -outcome
            results.append(agent_score)
        score = np.concatenate(results)
        return {"win_rate": float((score > 0).mean()),
                "draw_rate": float((score == 0).mean()),
                "non_loss_rate": float((score >= 0).mean())}


@dataclass
class AlphaZeroConfig:
    """ref: alpha_zero.py AlphaZeroConfig (num_sims, puct c, Dirichlet
    noise, temperature schedule)."""
    game: str = "TicTacToe-v0"
    num_workers: int = 2
    games_per_worker: int = 8
    num_sims: int = 32
    c_puct: float = 1.5
    temperature_moves: int = 4    # sample from visits for the first k
    dirichlet_alpha: float = 0.6
    dirichlet_eps: float = 0.25
    lr: float = 1e-3
    train_batch_size: int = 256
    num_updates_per_iter: int = 8
    replay_capacity: int = 20_000
    hidden: tuple = (64, 64)
    seed: int = 0
    worker_resources: Dict[str, float] = field(default_factory=dict)

    def build(self) -> "AlphaZero":
        return AlphaZero(self)


class AlphaZeroLearner:
    """pi: visit-count cross-entropy; v: outcome MSE — one fused scan."""

    def __init__(self, obs_dim: int, num_actions: int, c: AlphaZeroConfig):
        import functools

        import jax
        import jax.numpy as jnp
        import optax

        from .models import forward, init_policy_params

        self.params = init_policy_params(
            jax.random.PRNGKey(c.seed), obs_dim, num_actions,
            tuple(c.hidden))
        self.optimizer = optax.adam(c.lr)
        self.opt_state = self.optimizer.init(self.params)

        def loss_fn(params, mb):
            logits, values = forward(params, mb["obs"])
            logp = jax.nn.log_softmax(logits)
            pol = -jnp.mean(jnp.sum(mb["pi"] * logp, axis=1))
            val = jnp.mean((jnp.tanh(values) - mb["z"]) ** 2)
            return pol + val, {"policy_loss": pol, "value_loss": val}

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def update_many(params, opt_state, batches):
            def body(carry, mb):
                params, opt_state = carry
                (loss, stats), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, mb)
                updates, opt_state = self.optimizer.update(grads,
                                                           opt_state)
                params = optax.apply_updates(params, updates)
                return (params, opt_state), {**stats, "loss": loss}

            (params, opt_state), stats = jax.lax.scan(
                body, (params, opt_state), batches)
            return params, opt_state, jax.tree.map(jnp.mean, stats)

        self._update_many = update_many

    def update(self, stacked: Dict[str, np.ndarray]) -> Dict[str, float]:
        import jax
        import jax.numpy as jnp

        jb = {k: jnp.asarray(v) for k, v in stacked.items()}
        self.params, self.opt_state, stats = self._update_many(
            self.params, self.opt_state, jb)
        return {k: float(v) for k, v in jax.device_get(stats).items()}

    def get_params(self) -> Dict:
        import jax

        return jax.device_get(self.params)


class AlphaZero:
    """Self-play driver: parallel MCTS workers -> replay of
    (obs, pi, z) -> fused learner -> weight broadcast."""

    def __init__(self, config: AlphaZeroConfig):
        from .replay_buffer import ReplayBuffer

        self.config = c = config
        game = _GAMES[c.game]
        cls = ray_tpu.remote(AlphaZeroSelfPlayWorker)
        opts = worker_opts(c.worker_resources)
        self.workers = [
            cls.options(**opts).remote(
                c.game, c.games_per_worker, c.num_sims, c.c_puct,
                c.temperature_moves, c.dirichlet_alpha, c.dirichlet_eps,
                seed=c.seed + 101 * i)
            for i in range(c.num_workers)]
        self.learner = AlphaZeroLearner(game.OBS_DIM, game.A, c)
        self.buffer = ReplayBuffer(c.replay_capacity, seed=c.seed)
        self._iteration = 0
        self._total_games = 0

    def train(self) -> Dict[str, Any]:
        c = self.config
        t0 = time.monotonic()
        params_ref = ray_tpu.put(self.learner.get_params())
        outs = ray_tpu.get(
            [w.self_play.remote(params_ref) for w in self.workers],
            timeout=600)
        games, x_scores = 0, []
        for o in outs:
            games += int(o.pop("games"))
            x_scores.append(float(o.pop("x_score")))
            self.buffer.add(o)
        self._total_games += games
        stats: Dict[str, float] = {}
        # gate until one full batch exists (the sac.py pattern): a
        # shrunken B would recompile the jitted scan per new shape and
        # train on heavily duplicated rows
        if len(self.buffer) >= c.train_batch_size:
            K, B = c.num_updates_per_iter, c.train_batch_size
            mb = self.buffer.sample(K * B)
            stacked = {k: v.reshape(K, B, *v.shape[1:])
                       for k, v in mb.items()}
            stats = self.learner.update(stacked)
        self._iteration += 1
        return {"training_iteration": self._iteration,
                "games_total": self._total_games,
                "games_this_iter": games,
                "x_score_mean": float(np.mean(x_scores)),
                "buffer_positions": len(self.buffer),
                "time_this_iter_s": time.monotonic() - t0,
                **stats}

    def evaluate_vs_random(self, num_games: int = 32,
                           seed: int = 7) -> Dict[str, float]:
        params_ref = ray_tpu.put(self.learner.get_params())
        return ray_tpu.get(
            self.workers[0].evaluate_vs_random.remote(
                params_ref, num_games, seed), timeout=600)

    # -- Tune-trainable surface ------------------------------------------

    def save(self) -> Dict:
        import jax

        return {"params": jax.device_get(self.learner.params),
                "opt_state": jax.device_get(self.learner.opt_state),
                "iteration": self._iteration,
                "total_games": self._total_games,
                "buffer": self.buffer.state()}

    def restore(self, ckpt: Dict) -> None:
        import jax
        import jax.numpy as jnp

        self.learner.params = jax.tree.map(jnp.asarray, ckpt["params"])
        if "opt_state" in ckpt:
            self.learner.opt_state = jax.tree.map(jnp.asarray,
                                                  ckpt["opt_state"])
        self._iteration = int(ckpt.get("iteration", 0))
        self._total_games = int(ckpt.get("total_games", 0))
        if "buffer" in ckpt:
            self.buffer.restore(ckpt["buffer"])

    def stop(self) -> None:
        for w in self.workers:
            try:
                ray_tpu.kill(w)
            except Exception:
                pass
