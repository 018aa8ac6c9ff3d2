"""run_with_deadline streaming: a killed child must leave a visible tail.

A dryrun child's output used to be buffered in a temp file and only
flushed after exit — a driver-side kill left an empty tail. stream=True
tees output as it is produced, so these tests pin that a
deadline kill still surfaces everything printed before the kill.
"""

from __future__ import annotations

import sys

from metaopt_tpu.utils.procs import run_many_with_deadline, run_with_deadline


def test_stream_tees_output_live(capfd):
    code = "print('alpha', flush=True); print('beta', flush=True)"
    rc, out = run_with_deadline(
        [sys.executable, "-c", code], timeout_s=30.0,
        capture=True, stream=True, poll_s=0.1,
    )
    assert rc == 0
    assert "alpha" in out and "beta" in out
    teed = capfd.readouterr().out
    assert "alpha" in teed and "beta" in teed


def test_stream_survives_deadline_kill(capfd):
    # child prints progress then hangs: the kill must not eat the progress
    code = "import time; print('step-1 done', flush=True); time.sleep(60)"
    rc, out = run_with_deadline(
        [sys.executable, "-c", code], timeout_s=2.0,
        capture=True, stream=True, poll_s=0.1,
    )
    assert rc is None  # deadline hit
    assert "step-1 done" in out
    assert "step-1 done" in capfd.readouterr().out


def test_capture_without_stream_unchanged(capfd):
    rc, out = run_with_deadline(
        [sys.executable, "-c", "print('quiet')"], timeout_s=30.0, capture=True,
    )
    assert rc == 0 and "quiet" in out
    assert capfd.readouterr().out == ""  # no tee unless stream=True


def test_many_labels_prefix_and_results(capfd):
    jobs = [
        ("one", [sys.executable, "-c", "print('from-one', flush=True)"], None),
        ("two", [sys.executable, "-c",
                 "print('from-two', flush=True); raise SystemExit(3)"], None),
    ]
    results = run_many_with_deadline(jobs, timeout_s=30.0, poll_s=0.1)
    assert results["one"][0] == 0 and "from-one" in results["one"][1]
    assert results["two"][0] == 3 and "from-two" in results["two"][1]
    teed = capfd.readouterr().out
    assert "[one] from-one" in teed
    assert "[two] from-two" in teed


def test_many_shared_deadline_kills_and_keeps_tail(capfd):
    # the fast job finishes; the hanging job is killed with rc None, and
    # everything it printed before the kill stays visible (the dryrun's
    # tail-on-driver-kill doctrine, multiplexed)
    jobs = [
        ("fast", [sys.executable, "-c", "print('fast-done', flush=True)"],
         None),
        ("hang", [sys.executable, "-c",
                  "import time; print('hang-progress', flush=True); "
                  "time.sleep(60)"], None),
    ]
    results = run_many_with_deadline(jobs, timeout_s=2.0, poll_s=0.1)
    assert results["fast"][0] == 0
    assert results["hang"][0] is None  # shared deadline hit
    assert "hang-progress" in results["hang"][1]
    teed = capfd.readouterr().out
    assert "[fast] fast-done" in teed and "[hang] hang-progress" in teed


def test_many_flushes_partial_last_line(capfd):
    # no trailing newline before the hang: the final drain must still
    # surface the partial line under its label
    jobs = [
        ("p", [sys.executable, "-c",
               "import sys, time; sys.stdout.write('no-newline'); "
               "sys.stdout.flush(); time.sleep(60)"], None),
    ]
    results = run_many_with_deadline(jobs, timeout_s=2.0, poll_s=0.1)
    assert results["p"][0] is None
    assert "no-newline" in results["p"][1]
    assert "[p] no-newline" in capfd.readouterr().out
