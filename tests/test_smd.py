"""SMD: determinism, energy accounting, and the paper's SMD>=SMB claim."""
import dataclasses

import jax
import numpy as np
import pytest
pytest.importorskip("hypothesis")   # optional dev dep (requirements-dev.txt)
from hypothesis import given, settings, strategies as st

from bench.families.cifar_cnn import TRAIN_SEED
from repro.core import smd as smd_mod
from repro.core.config import (E2TrainConfig, Experiment, ModelConfig,
                               SMDConfig, TrainConfig)
from repro.core.smd import (SMDIterator, expected_energy_ratio, smd_keep,
                            smd_keep_host, smd_schedule)


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 1000), step=st.integers(0, 10000))
def test_smd_decision_deterministic(seed, step):
    """Counter-based: every host computes the same decision (straggler/FT)."""
    a = smd_keep_host(seed, step, 0.5)
    b = smd_keep_host(seed, step, 0.5)
    assert a == b


# either side of the first block edge, and a step near the int32 limit
PARITY_STEPS = (0, 255, 256, 257, 2 ** 31 - 2)


@pytest.mark.parametrize("drop_prob", [0.5, 0.3])
@pytest.mark.parametrize("seed", sorted({0, TRAIN_SEED, 2 ** 31 - 1,
                                         2 ** 31 + 5}))
@pytest.mark.parametrize("step", PARITY_STEPS)
def test_host_decision_matches_traced_smd_keep(seed, drop_prob, step):
    """The host's cached CPU blocks give ``smd_keep``'s decision on the
    default device, bit for bit, seeds >= 2**31 included."""
    want = bool(jax.jit(smd_keep, static_argnums=(0, 2))(seed, step,
                                                         drop_prob))
    assert smd_keep_host(seed, step, drop_prob) is want
    if step < 2 ** 16:
        sched = smd_schedule(SMDConfig(enabled=True, drop_prob=drop_prob),
                             seed, step + 1)
        assert bool(sched[step]) is want
    block = smd_mod._decide_block(seed, drop_prob, step // smd_mod._BLOCK)
    assert {d.platform for d in block.devices()} == {"cpu"}


def test_smd_drop_rate():
    sched = smd_schedule(SMDConfig(enabled=True, drop_prob=0.5), 0, 2000)
    rate = 1.0 - sched.mean()
    assert 0.45 < rate < 0.55


def test_smd_energy_ratio_paper_operating_point():
    """Paper Fig. 3a: SMD at 1.33x epochs = 0.67 energy ratio."""
    cfg = SMDConfig(enabled=True, drop_prob=0.5)
    assert abs(expected_energy_ratio(cfg, 4.0 / 3.0) - 2.0 / 3.0) < 1e-9


def test_smd_iterator_skips_without_fetch():
    fetched = []

    def gen():
        i = 0
        while True:
            fetched.append(i)
            yield i
            i += 1

    it = SMDIterator(gen(), SMDConfig(enabled=True, drop_prob=0.5), seed=0)
    out = [next(it) for _ in range(100)]
    dropped = sum(1 for _, b in out if b is None)
    assert dropped > 20
    assert len(fetched) == 100 - dropped  # dropped steps never fetched


def _train(exp, steps, seed=0):
    from repro.data.synthetic import MarkovLMTask, make_lm_batch
    from repro.training.train_step import init_train_state
    from repro.training.trainer import Trainer
    task = MarkovLMTask(vocab=exp.model.vocab_size)
    mk = lambda s, sh: make_lm_batch(task, 0, s, sh, exp.train.global_batch,
                                     exp.train.seq_len)
    state = init_train_state(jax.random.PRNGKey(seed), exp)
    tr = Trainer(exp, state, mk)
    hist = tr.run(steps)
    return hist, tr


@pytest.mark.slow
def test_smd_vs_smb_matched_budget():
    """Paper §4.2: at the same executed-step budget, SMD (spread over more
    nominal steps, sampling-with-replacement) matches or beats SMB."""
    model = ModelConfig(name="t", family="dense", num_layers=2, d_model=64,
                        num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=64,
                        dtype="float32")
    base = Experiment(model=model,
                      train=TrainConfig(global_batch=16, seq_len=32, lr=0.1,
                                        total_steps=120, schedule="constant"))
    smb_exp = base
    h_smb, _ = _train(smb_exp, 60)
    smd_exp = base.replace(e2=E2TrainConfig(smd=SMDConfig(True, 0.5)))
    h_smd, tr = _train(smd_exp, 120)
    # matched executed budget (~60 steps each)
    assert 40 <= tr.executed_steps <= 80
    smb_final = np.mean([h["loss"] for h in h_smb[-10:]])
    smd_final = np.mean([h["loss"] for h in h_smd[-10:]])
    assert smd_final < smb_final * 1.15, (smb_final, smd_final)
