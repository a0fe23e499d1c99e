"""``correct`` comes out true for a sound run and false for the control and
for every fault a cell can have.  Each case skips the harness's look for a
chip and drives the rest of a run (``run.run_cell``) at a size a CPU holds,
with the timed path broken underneath."""

import numpy as np
import pytest

from benchmark import run
from benchmark.drivers import serve as serve_driver
from benchmark.drivers import train as train_driver

from .conftest import CPU


def _train(cell, monkeypatch, break_step=None):
    if break_step is not None:
        build = train_driver.build
        # the wrapped step has no .lower for the trainer's AOT memory plan
        monkeypatch.setenv("RELORA_TPU_LIVE_MFU", "0")

        def broken_build(*a, **kw):
            trainer, factory = build(*a, **kw)
            trainer._train_step = break_step(trainer._train_step)
            return trainer, factory

        monkeypatch.setattr(train_driver, "build", broken_build)
    return run.run_cell(cell, seed=2**31 + 11, seconds=0.5, trace=False, device=CPU)


def state_unchanged(step):
    import jax
    import jax.numpy as jnp

    def fake(state, batch, rng):
        _, metrics = step(jax.tree_util.tree_map(jnp.copy, state), batch, rng)
        return state, metrics

    return fake


def half_batch(step):
    return lambda state, batch, rng: step(state, batch[:, : batch.shape[1] // 2], rng)


def test_train_sound_run_is_correct(tiny_cell, monkeypatch):
    res = _train(tiny_cell("train.tiny"), monkeypatch)
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "compared" and set(res["compared"]) >= {"loss_3_gap", "grad_norm_gap", "change_norm_gap"}
    assert res["metrics"]["train_tokens_per_s"]["value"] > 0 and res["metrics"]["setup_s"]["value"] > 0


@pytest.mark.parametrize("fault", [state_unchanged, half_batch])
def test_train_fault_is_not_correct(tiny_cell, monkeypatch, fault):
    res = _train(tiny_cell("train.tiny"), monkeypatch, fault)
    assert res["correct"] is False
    over = {n for n, c in res["compared"].items() if not c["value"] <= c["limit"]}
    assert over & {"grad_norm_gap", "change_norm_gap", "loss_3_gap"}


def test_train_control_in_fp8_is_not_correct(tiny_cell):
    from benchmark import harness

    cell = tiny_cell("train.tiny")
    for seed in (3, 4, 5):
        batches = [np.random.RandomState(seed + i).randint(0, 1024, size=(4, 65)) for i in range(3)]
        reference = train_driver.run_reference(cell, seed, batches)
        control = train_driver.run_reference(cell, seed, batches, cast="fp8")
        check = harness.Check()
        train_driver.compare(check, control, reference, cell.workload["limits"])
        assert not check.correct, check.as_dict()
        sound = harness.Check()
        train_driver.compare(sound, train_driver.run_reference(cell, seed, batches), reference, cell.workload["limits"])
        assert sound.correct


def _serve(cell, monkeypatch, alter=False):
    if alter:
        from relora_tpu.serve.scheduler import PagedContinuousBatchingScheduler as S

        real, calls = S._sample_rows, [0]

        def altered(self, logits, slots):
            tokens = np.array(real(self, logits, slots))
            calls[0] += 1
            return (tokens + 1) % 1024 if calls[0] % 5 == 0 else tokens

        monkeypatch.setattr(S, "_sample_rows", altered)
    return run.run_cell(cell, seed=2**31 + 13, seconds=2.0, trace=False, device=CPU)


def test_serve_sound_run_is_correct(tiny_cell, monkeypatch):
    res = _serve(tiny_cell("serve.tiny"), monkeypatch)
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"serve_tokens_per_s", "ttft_p95_ms", "tpot_p95_ms", "setup_s"}


def test_serve_altered_token_is_not_correct(tiny_cell, monkeypatch):
    res = _serve(tiny_cell("serve.tiny"), monkeypatch, alter=True)
    assert res["correct"] is False
    assert res["compared"]["served_logit_gap"]["value"] > res["compared"]["served_logit_gap"]["limit"]


def test_serve_control_in_fp8_is_not_correct(tiny_cell):
    cell = tiny_cell("serve.tiny")
    limit = cell.workload["limits"]["served_logit_gap"]
    for seed in (3, 4, 5):
        rs = np.random.RandomState(seed)
        sample = [{"prompt": rs.randint(0, 1024, size=40).tolist(), "tokens": rs.randint(0, 1024, size=16).tolist()} for _ in range(3)]
        assert serve_driver.served_gap(cell, seed, sample, cast="fp8")["gap"] > limit


def test_tokens_in_window_counts_edge_tokens_by_their_share():
    times = [1.0, 2.0, 3.0, 4.0]
    assert serve_driver.tokens_in_window(times, 0.0, 10.0) == 4.0
    assert serve_driver.tokens_in_window(times, 1.5, 3.5) == 0.5 + 1.0 + 0.5
    assert serve_driver.tokens_in_window(times, 5.0, 9.0) == 0.0
    assert serve_driver.tokens_in_window([], 0.0, 1.0) == 0.0
