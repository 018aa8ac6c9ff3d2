"""The runner's copy of the train loop against the program's own: after N
steps from one seed both hold the same loss, so the copy cannot drift."""

import json
import os

from chipbench import run as harness
from chipbench.runners.steady_steps import Loop, model_hparams

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_same_loss_as_train_and_eval_after_n_steps():
    from metaopt_tpu.models.transformer import train_and_eval

    config = json.load(open(os.path.join(
        ROOT, "chipbench", "configs", "transformer-base-wmt.json")))
    harness.rehearsal_sizes(config)
    n, seed = 7, 2 ** 31 + 3
    config["hparams"].update(schedule_steps=n, warmup=2)
    a, hp = config["script_args"], model_hparams(config)

    with Loop(a, hp, seed) as loop:
        for i in range(n):
            loop.step(i)
        loop.drain()
    want = train_and_eval(hp, tp=a["tp"], n_train=a["n_train"],
                          batch_size=a["batch_size"], seq_len=a["seq_len"],
                          steps=n, seed=seed)
    assert float(loop.losses[-1]) == want
