"""The operation and byte counts of ``_fused_step`` against a hand count."""
from __future__ import annotations

import pathlib
import sys

import jax
import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import flops  # noqa: E402

#: a tiny network: encoder 11 -> 4 -> 4 -> 4 -> 3, two LSTM layers of 2
NET = {"enc_hidden": 4, "enc_out": 3, "lstm_hidden": 2, "lstm_layers": 2,
       "horizon": 2}
HOST_DIM, TASK_DIM, NB = 6, 5, 3


def test_fused_step_flops_by_hand():
    # host block through layer 1 once a step: 2 * T(2) * 6 * 4 = 96
    # task block through layer 1 once a job:  2 * nb(3) * 5 * 4 = 120
    # per (step, job) row: layers 2-4 2*(16 + 16 + 12) = 88,
    #   LSTM 2*(3*8 + 2*8) + 2*(2*8 + 2*8) = 80 + 64, head 2*2*2 = 8
    #   -> 240, times T * nb = 6 rows -> 1440
    assert flops.fused_step_flops(NB, HOST_DIM, TASK_DIM, NET) \
        == 96 + 120 + 1440


def test_decision_flops_count_the_head_once():
    # as above at 3 real jobs, but the head once per job: 240 - 8 per
    # row, plus 8 per job
    assert flops.decision_flops(NB, HOST_DIM, TASK_DIM, NET) \
        == 96 + 120 + 6 * 232 + 3 * 8
    assert flops.decision_flops(0, HOST_DIM, TASK_DIM, NET) == 0


def test_param_count_matches_the_program_tree():
    from repro.core import encoder_lstm as net
    tree = net.init_params(jax.random.PRNGKey(0), HOST_DIM + TASK_DIM,
                           enc_hidden=4, enc_out=3, lstm_hidden=2,
                           lstm_layers=2)
    leaves = jax.tree_util.tree_leaves(tree)
    assert flops.param_count(HOST_DIM + TASK_DIM, NET) == \
        sum(int(np.prod(x.shape)) for x in leaves) == 197


def test_fused_step_bytes_by_hand():
    # 197 weights + ring in and out (2 * 2 * 6) + staged vector
    # (2 + 6 + 3 * (1 + 5)) + E_S (3), four bytes each
    assert flops.fused_step_bytes(NB, HOST_DIM, TASK_DIM, NET) \
        == 4 * (197 + 24 + 26 + 3)
