"""``correct`` has to come out false: for the control (the reference one
precision down in the program's place), and for a run whose timed path is
broken underneath. All at a size a test run can hold; the readings the
limits were set from, at the cell's own size on the chip, are in PERF.md.
"""

import time

import pytest

from chipbench import checks, run as harness
from chipbench.runners import steady_steps

CELL = "transformer-base.steady"


def context(tmp_path, seed=2 ** 31 + 21):
    """A rehearsal's context: tiny sizes, and no look for a chip."""
    _, ctx = harness.cell_context(CELL, seed, 1.0, False, True, time.time())
    ctx.run_dir = str(tmp_path)
    return ctx


def test_the_control_fails_where_the_program_passes(tmp_path):
    """fp8 products in the reference's place break a limit of the check, by
    the number that separates it from the program at this size too."""
    ctx = context(tmp_path, seed=11)
    first = steady_steps.readings(ctx)
    rows = first.pop("rows")
    program = checks.run(ctx.config, ctx.seed, rows, first)
    control = checks.run(ctx.config, ctx.seed, rows)
    assert program["correct"], program["numbers"]
    assert not control["correct"]
    assert control["numbers"]["grad_rms_gap"]["value"] \
        > 3 * program["numbers"]["grad_rms_gap"]["value"]


def broken_step(monkeypatch, wrap):
    """``make_train_step`` with ``wrap(inner_step)`` in the step's place."""
    from metaopt_tpu.models import transformer

    real = transformer.make_train_step
    monkeypatch.setattr(transformer, "make_train_step",
                        lambda model, tx: wrap(real(model, tx)))


def lazy(inner):
    def step(params, opt_state, batch, key):
        _, _, loss = inner(params, opt_state, batch, key)
        return params, opt_state, loss
    return step


def half_batch(inner):
    def step(params, opt_state, batch, key):
        half = batch[0].shape[0] // 2
        src, tgt = batch
        # the second half of the rows repeats the first: half the batch
        # is left out, and the shapes stay what the loop feeds
        twice = lambda x: x.at[half:].set(x[:half])  # noqa: E731
        return inner(params, opt_state, (twice(src), twice(tgt)), key)
    return step


@pytest.mark.parametrize("wrap, number", [
    (lazy, "update_norm_gap"), (half_batch, "loss_gap")],
    ids=["state_returned_unchanged", "half_the_batch_left_out"])
def test_a_broken_timed_step(tmp_path, monkeypatch, wrap, number):
    """The runner end to end, without its look for a chip: sound first,
    then with the step the window times broken underneath."""
    sound = steady_steps.run(context(tmp_path))
    assert sound["correct"], sound["records"]["check"]
    broken_step(monkeypatch, wrap)
    broken = steady_steps.run(context(tmp_path))
    assert not broken["correct"]
    assert not broken["records"]["check"]["numbers"][number]["ok"]
