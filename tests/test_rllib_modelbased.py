"""Model-based / planning RLlib families: AlphaZero (MCTS self-play),
Dreamer (world model + imagination), the AlphaStar league. Reference
analogues: rllib/algorithms/{alpha_zero,dreamer,alpha_star}/. MAML and
SlateQ are in test_rllib_maml_slateq.py: under `--dist loadfile` a file
is the unit of work, and the five learning tests in one file were 334 s
on one worker.

Each gets a learning test with an explicit threshold plus the
machinery checks (checkpoint round-trip, decomposition invariants).
"""

import numpy as np
import pytest


def test_alpha_zero_learns_tictactoe():
    from ray_tpu.rllib.algorithms.alpha_zero import AlphaZeroConfig
    algo = (AlphaZeroConfig().environment("tictactoe")
            .training(games_per_iteration=24, num_sims=32, sgd_iters=8,
                      lr=2e-3)
            .debugging(seed=0).build())
    for _ in range(24):
        r = algo.step()
    assert np.isfinite(r["learner/total_loss"])
    # the RAW NET (no search) must beat a random opponent decisively —
    # that isolates what self-play taught the policy/value net
    net = algo.play_vs_random(30, use_search=False, seed=7)
    assert net["win_rate"] + net["draw_rate"] >= 0.85, net
    assert net["loss_rate"] <= 0.15, net
    # with search on top it should be at least as strong
    search = algo.play_vs_random(20, use_search=True, seed=11)
    assert search["win_rate"] + search["draw_rate"] >= 0.85, search
    st = algo.save_checkpoint()
    algo.load_checkpoint(st)
    assert algo.play_vs_random(10, seed=3)["loss_rate"] <= 0.3


def test_alpha_zero_connect4_machinery():
    """Self-play + update runs on the bigger game; terminal detection
    must see all four win directions."""
    from ray_tpu.rllib.algorithms.alpha_zero import (AlphaZeroConfig,
                                                     Connect4)
    g = Connect4()
    # vertical win: player 1 stacks column 0 (player -1 plays col 1)
    s = g.initial_state()
    for _ in range(3):
        s = g.next_state(s, 0)
        s = g.next_state(s, 1)
    s = g.next_state(s, 0)  # fourth in a row, mover flips to -1
    assert g.terminal_value(s) == -1.0  # the player to move lost
    algo = (AlphaZeroConfig().environment("connect4")
            .training(games_per_iteration=2, num_sims=8, sgd_iters=1)
            .debugging(seed=0).build())
    r = algo.step()
    assert r["num_env_steps_sampled_this_iter"] > 0
    assert np.isfinite(r["learner/total_loss"])


def test_mcts_prefers_winning_move():
    """Search alone (uniform net) must find an immediate win."""
    from ray_tpu.rllib.algorithms.alpha_zero import MCTS, TicTacToe
    g = TicTacToe()
    # X to move with two in a row: playing cell 2 wins
    board = np.zeros(9, np.int8)
    board[0] = board[1] = 1
    board[3] = board[4] = -1
    state = (board, 1)

    def uniform_eval(obs):
        return (np.zeros((obs.shape[0], 9), np.float32),
                np.zeros((obs.shape[0],), np.float32))

    counts = MCTS(g, uniform_eval,
                  rng=np.random.default_rng(0)).run(
        state, 200, add_noise=False)
    assert int(np.argmax(counts)) == 2, counts


def test_dreamer_learns_pendulum_balance():
    from ray_tpu.rllib.algorithms.dreamer import DreamerConfig
    algo = (DreamerConfig()
            .environment("Pendulum-v1", env_config={"balance_init": True})
            .training(prefill_steps=600).debugging(seed=0).build())
    untrained = algo.evaluate(4)["evaluation"]["episode_reward_mean"]
    first = None
    for i in range(25):
        r = algo.step()
        if first is None and "learner/recon_loss" in r:
            first = r
    # world model must actually fit: recon + reward losses shrink
    assert r["learner/recon_loss"] < first["learner/recon_loss"] * 0.7
    assert r["learner/reward_loss"] < first["learner/reward_loss"]
    trained = algo.evaluate(4)["evaluation"]["episode_reward_mean"]
    assert trained > untrained + 150, (untrained, trained)
    assert trained > -850, trained
    st = algo.save_checkpoint()
    algo.load_checkpoint(st)
    again = algo.evaluate(2)["evaluation"]["episode_reward_mean"]
    assert np.isfinite(again)


def test_alpha_star_league_learns_and_cycles():
    """League self-play (reference: alpha_star league_builder +
    distributed training shape): the main agent must (a) beat a random
    player, (b) beat its own first snapshot (real progress, not noise),
    while the league accrues historical snapshots and a populated
    payoff matrix with exploiters applying pressure."""
    from ray_tpu.rllib.algorithms import AlphaStar, AlphaStarConfig
    from ray_tpu.rllib.algorithms.alpha_star import (
        HISTORICAL, MAIN, pfsp_weights)
    import numpy as np

    algo = AlphaStar(AlphaStarConfig().to_dict()
                     | {"seed": 0, "matches_per_iter": 48,
                        "snapshot_interval": 8})
    last = {}
    rates = []
    for _ in range(24):
        last = algo.step()
        rates.append(last["main_vs_random_win_rate"])
    assert max(rates[-6:]) >= 0.7, rates
    assert sum(rates[-6:]) / 6 >= 0.6, rates

    roles = {p.ptype for p in algo.league.values()}
    assert HISTORICAL in roles and "main_exploiter" in roles \
        and "league_exploiter" in roles
    assert last["num_historical"] >= 2
    # payoff matrix drives PFSP and shows main beating its oldest self
    # (EMA over every PFSP match against it — hundreds of samples)
    assert algo.payoff[MAIN]["historical_0"] > 0.5

    # pfsp weighting prefers hard opponents
    w = pfsp_weights(np.array([0.9, 0.5, 0.1]))
    assert w[2] > w[1] > w[0]

    # checkpoint round-trips the WHOLE league (roster, payoff,
    # snapshot counter), not just main's params
    ckpt = algo.save_checkpoint()
    fresh = AlphaStar(AlphaStarConfig().to_dict() | {"seed": 1})
    fresh.load_checkpoint(ckpt)
    assert set(fresh.league) == set(algo.league)
    assert fresh._snapshots == algo._snapshots
    assert fresh.payoff[MAIN].keys() == algo.payoff[MAIN].keys()
    assert fresh.eval_vs_random(MAIN, 10) >= 0.5  # restored, not fresh
